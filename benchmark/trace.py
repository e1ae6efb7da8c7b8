"""From the JAX profiler's trace to device times: the reduction, kept as code.

Two stages, so that the second can be checked against a small recorded
fixture without a chip and without jax:

``extract(xplane)``  reads the ``.xplane.pb`` (this needs jax) into plain
                     lists: per device the module runs and the ops, and the
                     host's named spans, all in seconds on the trace's clock.
``reduce(events)``   busy time as the union of op intervals, the window,
                     time and runs per executable, the ops with most time
                     named ``<executable>:<op>_<dtype>_<shape>``, and the
                     longest idle gaps by the host span that covers them.

    python3 benchmark/trace.py <file.xplane.pb> <out.json> [--events <file>]
    python3 benchmark/trace.py --describe <file.xplane.pb>
"""

from __future__ import annotations

import bisect
import gzip
import json
import re
import sys

SMALL_GAP_S = 20e-6
TOP = 10
_RESULT_RE = re.compile(r"^\(?(\w+)\[([\d,]*)\]")
_KIND_RE = re.compile(r"kind=(k\w+)")
# ops that only hold other ops: their time is their children's, so they
# stay out of the list of ops with most time (never out of busy time)
CONTAINERS = ("while", "conditional", "call")
NAME_CHARS = 400


def extract(xplane_path: str) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(xplane_path)
    devices, host = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = {"name": plane.name, "modules": [], "ops": []}
            for line in plane.lines:
                if line.name == "XLA Modules":
                    dev["modules"] = [
                        [e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9]
                        for e in line.events
                    ]
                elif line.name == "XLA Ops":
                    for e in line.events:
                        stats = dict(e.stats)
                        text = str(stats.get("long_name")
                                   or stats.get("hlo_text") or "")
                        dev["ops"].append([e.name[:NAME_CHARS],
                                           e.start_ns * 1e-9,
                                           e.duration_ns * 1e-9,
                                           text[:NAME_CHARS]])
            if dev["ops"]:
                devices.append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    # "$..." are the Python tracer's frames; the spans the
                    # program and the runtime name themselves are the rest
                    if e.duration_ns > 0 and not e.name.startswith("$"):
                        host.append([e.name, e.start_ns * 1e-9,
                                     e.duration_ns * 1e-9])
    if not devices:
        # a CPU rehearsal has no device plane: XLA's CPU ops carry their
        # module in a stat, on host threads. Never a device number.
        dev = {"name": "/host:CPU(rehearsal)", "modules": [], "ops": []}
        for plane in data.planes:
            for line in plane.lines:
                for e in line.events:
                    stats = dict(e.stats)
                    if "hlo_module" in stats and e.duration_ns > 0:
                        s, d = e.start_ns * 1e-9, e.duration_ns * 1e-9
                        dev["ops"].append([e.name, s, d, ""])
                        dev["modules"].append([str(stats["hlo_module"]), s, d])
        if dev["ops"]:
            devices.append(dev)
    return {"devices": devices, "host": host}


def _union(intervals: list) -> list:
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def executable_of(module_event_name: str) -> str:
    """``jit_fused_burst(1234)`` -> ``jit_fused_burst``."""
    return re.sub(r"\(\d+\)$", "", module_event_name)


def op_label(name: str, text: str = "") -> str:
    """A device op's label, ``<op>[_<kind>]_<dtype>_<shape>``. On a TPU the
    event's name is the HLO instruction itself:
    ``%fusion.12 = bf16[32,14336]{1,0:T(8,128)(2,1)} fusion(...), kind=kOutput``
    gives ``fusion_kOutput_bf16_32_14336``; a tuple result is labelled by
    its first element. Elsewhere the instruction may come as ``text``."""
    if " = " in name:
        name, text = name.split(" = ", 1)
    base = re.sub(r"[.\d]+$", "", name.lstrip("%")) or name
    kind = _KIND_RE.search(text)
    if kind:
        base += "_" + kind.group(1)
    res = _RESULT_RE.search(text.lstrip())
    if res:
        base += "_" + res.group(1)
        if res.group(2):
            base += "_" + res.group(2).replace(",", "_")
    return base


def reduce(events: dict) -> dict:
    devices = events["devices"]
    if not devices:
        return {}
    busy = window = 0.0
    modules: dict = {}
    ops: dict = {}
    gaps: dict = {}
    host = sorted(events["host"], key=lambda h: h[1])
    host_starts = [h[1] for h in host]
    for dev in devices:
        union = _union([[s, s + d] for _n, s, d, _t in dev["ops"]])
        t0, t1 = union[0][0], union[-1][1]
        busy += sum(e - s for s, e in union)
        window += t1 - t0
        mods = sorted(dev["modules"], key=lambda m: m[1])
        mod_starts = [m[1] for m in mods]
        for name, _s, d in mods:
            entry = modules.setdefault(executable_of(name),
                                       {"runs": 0, "seconds": 0.0})
            entry["runs"] += 1
            entry["seconds"] += d
        for name, s, d, text in dev["ops"]:
            i = bisect.bisect_right(mod_starts, s) - 1
            exe = "?"
            if i >= 0 and s < mods[i][1] + mods[i][2]:
                exe = executable_of(mods[i][0])
            label = op_label(name, text)
            if not label.startswith(CONTAINERS):
                label = f"{exe}:{label}"
                ops[label] = ops.get(label, 0.0) + d
        for (_s0, e0), (s1, _e1) in zip(union, union[1:]):
            gap = s1 - e0
            if gap < SMALL_GAP_S:
                what = "gaps_under_20_us"
            else:
                what = _host_span_at(host, host_starts, e0)
            gaps[what] = gaps.get(what, 0.0) + gap
    n = len(devices)
    for entry in modules.values():
        entry["seconds"] /= n
        entry["runs"] /= n

    def top(seconds: dict) -> list:
        ranked = sorted(seconds.items(), key=lambda kv: -kv[1])[:TOP]
        return [[k, v / n] for k, v in ranked]

    return {
        "chips": n, "busy_s": busy / n, "window_s": window / n,
        "modules": modules, "device_ops": top(ops), "idle_gaps": top(gaps),
    }


def _host_span_at(host: list, starts: list, t: float) -> str:
    """The shortest named host span that covers time ``t``."""
    best, best_d = "no_host_span", float("inf")
    i = bisect.bisect_right(starts, t)
    for name, s, d in host[max(0, i - 200):i]:
        if s <= t < s + d and d < best_d:
            best, best_d = name, d
    return best


def module_seconds(reduced: dict, *prefixes: str) -> tuple:
    """``(seconds, runs)`` of the executables whose name starts with one of
    the prefixes."""
    seconds = runs = 0.0
    for name, entry in (reduced.get("modules") or {}).items():
        if name.startswith(prefixes):
            seconds += entry["seconds"]
            runs += entry["runs"]
    return seconds, runs


PREFILL = ("jit_prefill_one", "jit_prefill_many")


def prefill_work(run: dict):
    """``(device seconds, padded tokens, sequences)`` of the prefills run
    while the trace was taken: the seconds from the trace, the rest from
    the batcher's counters read as it started and stopped. None where
    there was none."""
    seconds, runs = module_seconds(run["trace"] or {}, *PREFILL)
    if not runs or not run["trace_counters"]:
        return None
    a, b = (s["stats"] for s in run["trace_counters"])
    padded = b["prefill_tokens"] - a["prefill_tokens"]
    sequences = b["admitted"] - a["admitted"]
    if padded <= 0 or sequences <= 0:
        return None
    return seconds, padded, sequences


def describe(xplane_path: str, per_line: int = 4) -> None:
    """A trace looked at by hand: planes, lines, and a few events of each
    with their stats."""
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(xplane_path).planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            events = list(line.events)
            print("  LINE", line.name, len(events))
            for e in events[:per_line]:
                print("     ", e.name, e.start_ns, e.duration_ns, dict(e.stats))


def main(argv: list) -> int:
    if len(argv) == 3 and argv[1] == "--describe":
        describe(argv[2])
        return 0
    if len(argv) < 3:
        print(__doc__, file=sys.stderr)
        return 2
    events = extract(argv[1])
    if "--events" in argv:
        with gzip.open(argv[argv.index("--events") + 1], "wt") as f:
            json.dump(events, f)
    with open(argv[2], "w") as f:
        json.dump(reduce(events), f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
