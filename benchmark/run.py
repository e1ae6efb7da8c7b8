#!/usr/bin/env python3
"""The benchmark's entry: one run of one cell.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1|2>

This parent never imports jax. It writes the cell's model directory and
predictor spec from the configuration file and the architecture module
that file names (``benchmark/architectures/``), starts ``benchmark/child.py``
(the program's own ``engine_main``, which owns the chip), has it compared
with the plain reference, offers the cell's traffic over the wire, and
prints as the last line of stdout one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` and, traced,
``breakdown``. ``--trace 0`` reports the cell's end-to-end metrics,
``--trace 1`` its per-layer metrics from a capture inside the window.
``--trace 2`` reports both from one process: it is a ``--trace 0`` run up
to the moment the window closes, and then captures a few seconds of the
same traffic. Set-up is everything from process start to the window
opening. Without an accelerator, or outside the repo,
it exits non-zero and prints no result.

``--rehearse-cpu`` is the builder's rehearsal: a tiny model on the CPU, no
result line.
"""

from __future__ import annotations

import time

T0 = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import queue  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import signal  # noqa: E402
import socket  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
import urllib.error  # noqa: E402
import urllib.request  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

READY_TIMEOUT_S = 1150.0
SHUTDOWN_TIMEOUT_S = 60.0
# after the window: how long streams in flight may take to run to their end
# before the rest is cut (a full lane plus one queued request: two times 256
# tokens at under 20 ms)
FINISH_S = 20.0
TRACE_AFTER_S = 1.0     # into the window (--trace 1)
TRACE_FOR_S = 4.0
# --trace 2: how long after the window the capture waits for first tokens
# still on their way to requests due in the window
FIRST_TOKEN_WAIT_S = 3.0
# a compile this long once the load runs means a request waited on an
# executable the warm-up should have covered
SLOW_COMPILE_S = 1.0
READY_RE = re.compile(
    r"generateserver: .* ready \(.*\) platform=(?P<platform>\S+) "
    r"device_kind='(?P<kind>[^']*)' .* load_s=(?P<load>[\d.]+) "
    r"warm_s=(?P<warm>[\d.]+)"
)
COMPILED_RE = re.compile(
    r"Finished XLA compilation of (?P<name>\S+) in (?P<s>[\d.eE+-]+) sec"
)


class RunFailed(Exception):
    """The run cannot give a result."""


class NoDevice(RunFailed):
    """JAX found no accelerator, or fewer chips than the cell needs."""


def log(msg: str) -> None:
    print(f"[benchmark +{time.monotonic() - T0:6.1f}s] {msg}", flush=True)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class Child:
    """The chip-owning process and its control line."""

    def __init__(self, cmd: list, env: dict, log_path: str):
        if "jax" in sys.modules:
            raise RunFailed("the parent imported jax; it must stay off it")
        self.log_path = log_path
        self._log = open(log_path, "w")
        self.proc = subprocess.Popen(
            cmd, cwd=ROOT, env=env, stdin=subprocess.PIPE,
            stdout=subprocess.PIPE, stderr=self._log, text=True,
        )
        self._replies: queue.Queue = queue.Queue()
        threading.Thread(target=self._pump, daemon=True).start()

    def _pump(self) -> None:
        for line in self.proc.stdout:
            self._replies.put(line)
        self._replies.put(None)

    def reply(self, timeout: float) -> dict:
        try:
            line = self._replies.get(timeout=timeout)
        except queue.Empty:
            raise RunFailed(f"the child said nothing for {timeout:.0f}s") from None
        if line is None:
            raise RunFailed(f"the child exited {self.proc.wait()}")
        out = json.loads(line)
        if "error" in out:
            raise RunFailed(f"child: {out['error']}")
        return out

    def ask(self, command: str, timeout: float = 120.0) -> dict:
        self.proc.stdin.write(command + "\n")
        self.proc.stdin.flush()
        return self.reply(timeout)

    def stop(self) -> int:
        """SIGTERM, as the control plane stops a replica; waits it out."""
        try:
            # end of the control line: the child's reader thread sees the
            # end of its stdin and is gone before the interpreter exits
            self.proc.stdin.close()
        except OSError:
            pass
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(SHUTDOWN_TIMEOUT_S)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
        self._log.close()
        return self.proc.returncode


def http_status(port: int, path: str) -> int:
    try:
        with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}",
                                    timeout=5.0) as resp:
            return resp.status
    except urllib.error.HTTPError as e:
        return e.code
    except (urllib.error.URLError, OSError):
        return 0


def write_spec(run_dir: str, arch, cfg: dict, mix: dict, seed: int) -> str:
    """Model dir by the normal ``jax_config.json`` route, family and kwargs
    from the configuration's architecture module, and a GENERATE_SERVER
    predictor spec: the configuration's server settings, this cell's
    warm-up shapes, every other knob at the program's default."""
    from benchmark import traffic
    from seldon_core_tpu.graph.spec import (
        PredictorSpec, default_predictor, validate_predictor,
    )

    model_dir = os.path.join(run_dir, "model")
    os.makedirs(model_dir, exist_ok=True)
    with open(os.path.join(model_dir, "jax_config.json"), "w") as f:
        json.dump({"family": arch.FAMILY,
                   "config": arch.model_kwargs(cfg, seed)}, f)
    settings = dict(cfg["server"])
    settings["warmup_prompt_lens"] = ",".join(
        str(n) for n in traffic.prompt_lens(mix))
    settings["warmup_max_new_tokens"] = traffic.max_new(mix)
    spec = {
        "name": "benchmark",
        "graph": {
            "name": "lm", "type": "MODEL",
            "implementation": "GENERATE_SERVER", "modelUri": model_dir,
            "parameters": [
                {"name": k, "value": str(v),
                 "type": "INT" if isinstance(v, int) else "STRING"}
                for k, v in settings.items()
            ],
        },
    }
    validate_predictor(default_predictor(PredictorSpec.from_dict(spec)))
    path = os.path.join(run_dir, "spec.json")
    with open(path, "w") as f:
        json.dump(spec, f, indent=1)
    return path


def wait_ready(child: Child, port: int) -> dict:
    t0 = time.monotonic()
    while http_status(port, "/ready") != 200:
        if child.proc.poll() is not None:
            raise RunFailed(f"engine exited {child.proc.returncode} before /ready")
        if time.monotonic() - t0 > READY_TIMEOUT_S:
            raise RunFailed(f"/ready not 200 after {READY_TIMEOUT_S:.0f}s")
        time.sleep(0.25)
    with open(child.log_path, errors="replace") as f:
        ready = next((m for m in map(READY_RE.search, f) if m), None)
    if ready is None:
        raise RunFailed("no ready line naming the device in the engine log")
    return {"platform": ready["platform"], "kind": ready["kind"],
            "load_s": float(ready["load"]), "warm_s": float(ready["warm"])}


def slow_compiles(log_path: str, offset: int) -> list:
    """Compilations of a second or more that the engine's log shows from
    byte ``offset`` on: where the load started, so a request waited."""
    slow = set()
    with open(log_path, errors="replace") as f:
        f.seek(offset)
        for line in f:
            m = COMPILED_RE.search(line)
            if m and float(m["s"]) >= SLOW_COMPILE_S:
                slow.add((m["name"], round(float(m["s"]), 1)))
    return sorted(slow)


def log_tail(path: str, n: int = 30) -> str:
    with open(path, errors="replace") as f:
        # the engine's own lines and any traceback, not JAX's compile log
        lines = [ln.rstrip()[:400] for ln in f if ln.strip()
                 and "jax._src." not in ln and "cpu_aot_loader" not in ln]
    return "\n".join(lines[-n:])


def next_run_dir(base: str, tag: str) -> str:
    k = 0
    while os.path.exists(os.path.join(base, f"{tag}-{k}")):
        k += 1
    path = os.path.join(base, f"{tag}-{k}")
    os.makedirs(path)
    return path


def reduce_trace(trace_dir: str, run_dir: str, env: dict) -> dict:
    """In a process of its own, after the chip's owner has gone: reading
    the trace needs jax, and this parent stays off it."""
    found = [os.path.join(d, f) for d, _s, fs in os.walk(trace_dir)
             for f in fs if f.endswith(".xplane.pb")]
    if not found:
        raise RunFailed(f"the profiler left no .xplane.pb under {trace_dir}")
    out = os.path.join(run_dir, "trace_reduced.json")
    env = dict(env, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, os.path.join(HERE, "trace.py"), found[0], out,
         "--events", os.path.join(run_dir, "trace_events.json.gz")],
        cwd=ROOT, env=env, timeout=300, capture_output=True, text=True,
    )
    if done.returncode != 0:
        raise RunFailed(f"trace reduction failed: {done.stderr[-2000:]}")
    shutil.rmtree(trace_dir, ignore_errors=True)   # tens of MB a run
    with open(out) as f:
        return json.load(f)


def load_cell(workload: str, rehearse: bool) -> tuple:
    """``(manifest, cell, configuration, traffic mix)`` of a cell's name."""
    from benchmark import manifest

    man = manifest.load(ROOT)
    cell = manifest.cell(man, workload)
    cfg = manifest.config(ROOT, man, cell["config"])
    mix = manifest.traffic(ROOT, man, cell["traffic"])
    # found here, with the other files of the cell: a name with no module
    # is refused before anything is started
    arch = manifest.architecture(ROOT, man, cfg["architecture"])
    if rehearse:
        log("REHEARSAL on the CPU at a tiny size: proves nothing about the chip")
        cfg.update(arch.rehearsal(cfg))
        cfg["server"] = dict(cfg["server"], slots=min(4, cfg["server"]["slots"]))
    return man, cell, cfg, mix


class Engine:
    """The served cell: the child that owns the chip, brought up and
    checked; what every run, and the rate sweep, starts from."""

    def __init__(self, man: dict, cell: dict, cfg: dict, mix: dict, seed: int,
                 rehearse: bool, run_dir: str):
        from benchmark import client, manifest, traffic

        cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
            HERE, "_cache")
        self.env = dict(os.environ)
        self.env["JAX_PLATFORMS"] = "cpu" if rehearse else "tpu"
        self.env["JAX_LOG_COMPILES"] = "1"
        self.env.setdefault("TPU_LOG_DIR", "disabled")
        self.env["PYTHONPATH"] = ROOT + os.pathsep + self.env.get("PYTHONPATH", "")
        self.port = free_port()
        self.arch = manifest.architecture(ROOT, man, cfg["architecture"])
        spec_path = write_spec(run_dir, self.arch, cfg, mix, seed)
        self.child = Child(
            [sys.executable, os.path.join(HERE, "child.py"), "--spec", spec_path,
             "--http-port", str(self.port), "--chips", str(cell["chips"]),
             "--seed", str(seed), "--cache-dir", cache_dir,
             "--architecture", self.arch.__name__],
            self.env, os.path.join(run_dir, "engine.log"),
        )
        try:
            try:
                self.device = self.child.reply(timeout=300.0)["device"]
            except RunFailed as e:
                raise NoDevice(f"{e}\n{log_tail(self.child.log_path, 8)}") from e
            log(f"device {self.device}")
            self.peaks = manifest.peaks(
                ROOT, man, "TPU v5 lite" if rehearse else self.device["kind"])
            self.ready = wait_ready(self.child, self.port)
            log(f"ready: load {self.ready['load_s']}s, warm {self.ready['warm_s']}s")
            want = "cpu" if rehearse else "tpu"
            if self.ready["platform"] != want:
                raise RunFailed(
                    f"engine serves on {self.ready['platform']!r}, not {want!r}")
            # correct, part one: the plain reference, and a repeat
            self.reference = self.child.ask("reference", timeout=600.0)
            log(f"reference: {self.reference}")
            probe = traffic.prompt_tokens(seed, -1, traffic.prompt_lens(mix)[0],
                                          cfg["vocab_size"])
            once = client.generate_once(self.port, probe, 16)
            self.repeat_same = once == client.generate_once(self.port, probe, 16)
            log("greedy prompt sent twice: "
                + ("same" if self.repeat_same else "DIFFERENT"))
        except BaseException:
            self.child.stop()
            raise


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1, 2), default=0)
    parser.add_argument("--rehearse-cpu", action="store_true",
                        help="tiny model on the CPU; never prints a result")
    args = parser.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "seldon_core_tpu")):
        print("benchmark: no seldon_core_tpu/ beside benchmark/: it measures "
              "the repo it sits in", file=sys.stderr)
        return 2

    from benchmark import client, endtoend, manifest, traffic

    # ended from outside, the run still stops the child it started
    signal.signal(signal.SIGTERM, signal.default_int_handler)
    rehearse = args.rehearse_cpu
    try:
        man, cell, cfg, mix = load_cell(args.workload, rehearse)
    except manifest.ManifestError as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    slots = cfg["server"]["slots"]
    run_dir = next_run_dir(
        os.path.join(HERE, "_runs", cell["name"]),
        f"seed{args.seed}-trace{args.trace}",
    )
    try:
        engine = Engine(man, cell, cfg, mix, args.seed, rehearse, run_dir)
    except NoDevice as e:
        print(f"benchmark: no device: {e}", file=sys.stderr)
        return 3
    except (RunFailed, manifest.ManifestError, OSError, ValueError) as e:
        print(f"benchmark: FAILED: {e}", file=sys.stderr)
        return 1
    child, port, env = engine.child, engine.port, engine.env
    device, peaks, ready = engine.device, engine.peaks, engine.ready
    reference, repeat_same = engine.reference, engine.repeat_same
    load = None
    try:
        # -- load, ramp, window ---------------------------------------------
        log_offset = os.path.getsize(child.log_path)
        load = client.Load(port, mix, args.seed, cfg["vocab_size"], slots)
        load.start()
        ramp_until = load.t_start + float(mix["ramp_s"])
        steady = traffic.n_clients(mix, slots) if mix["loop"] == "closed" else 0
        while time.monotonic() < ramp_until or load.in_flight() < steady:
            if time.monotonic() > ramp_until + 30.0:
                raise RunFailed("the in-flight count never reached its level")
            time.sleep(0.01)
        snap_open = child.ask("snapshot")
        slo: list = []      # the scheduler's samples of the window's requests
        t_open = time.monotonic()
        setup_s = t_open - T0
        t_close = t_open + args.seconds
        log(f"window open after {setup_s:.1f}s of set-up")
        trace_window, trace_snaps = None, None
        trace_dir = os.path.join(run_dir, "trace")

        def capture(seconds: float) -> tuple:
            s0 = child.ask(f"trace_start {trace_dir}", timeout=300.0)
            time.sleep(seconds)
            s1 = child.ask("trace_stop", timeout=300.0)
            log(f"trace stopped in {s1['stop_s']:.1f}s")
            # the program's report, kept beside requests.jsonl
            with open(os.path.join(run_dir, "capture.json"), "w") as f:
                json.dump(s1.get("program"), f)
            return s0, s1

        if args.trace == 1:
            time.sleep(min(TRACE_AFTER_S, args.seconds / 4))
            s0, s1 = capture(
                max(0.5, min(TRACE_FOR_S, t_close - time.monotonic() - 1)))
            trace_window, trace_snaps = (s0["t"], s1["t"]), (s0, s1)
            slo += s0["slo"] + s1["slo"]
        time.sleep(max(0.0, t_close - time.monotonic()))
        snap_close = child.ask("snapshot")
        slo += snap_close["slo"]
        e2e = manifest.metrics_of(man, "end_to_end", cell["name"])
        if args.trace == 2:
            # up to here a --trace 0 run, and the load goes on. Of the
            # window's end-to-end numbers only a first token can still be
            # on its way, to a request due in the window: wait for those,
            # so that nothing the capture does can reach them.
            if any(m["name"].startswith("ttft") for m in e2e):
                waited = time.monotonic() + FIRST_TOKEN_WAIT_S
                while (pending := sum(
                        not r.first and not r.status for r in endtoend.due_in(
                            list(load.records), t_open, t_close))
                       ) and time.monotonic() < waited:
                    time.sleep(0.01)
                if pending:
                    # counted when it comes, as under --trace 0
                    log(f"{pending} first token(s) of the window still on "
                        f"their way after {FIRST_TOKEN_WAIT_S}s")
            # the profiler's first start and stop go into a trace that is
            # thrown away (about 4 s on the chip), so that their cost falls
            # into no number; then the same traffic is traced
            capture(0.0)
            shutil.rmtree(trace_dir, ignore_errors=True)
            s0, s1 = capture(TRACE_FOR_S)
            trace_window, trace_snaps = (s0["t"], s1["t"]), (s0, s1)
        time.sleep(float(mix.get("drain_s", 0)))
        load.stop(FINISH_S)
        log("load ended")
        snap_end = child.ask("snapshot")
        records = sorted(load.records, key=lambda r: r.index)
        with open(os.path.join(run_dir, "requests.jsonl"), "w") as f:
            f.write(json.dumps({"t_open": t_open, "t_close": t_close,
                                "seed": args.seed, "cell": cell["name"]}) + "\n")
            for r in records:
                f.write(json.dumps(r.to_json()) + "\n")
        t_stop = time.monotonic()
        rc = child.stop()
        log(f"engine exited {rc}, {time.monotonic() - t_stop:.1f}s after SIGTERM")
        slow = slow_compiles(child.log_path, log_offset)
        trace = None
        if args.trace:
            trace = reduce_trace(trace_dir, run_dir, env)

        # -- the numbers -----------------------------------------------------
        attempted, failed = endtoend.failures(records, t_open, t_close,
                                              mix["loop"] == "open")
        restarts = snap_end["stats"]["batcher_restarts"]
        # every reason is named: a run that reports ``correct: false``
        # says on stderr which check it failed
        wrong = []
        if not reference["ok"]:
            wrong.append(f"logits differ from the plain reference: {reference}")
        if not repeat_same:
            wrong.append("one greedy prompt sent twice gave different tokens")
        if attempted == 0:
            wrong.append("no request was due in the window")
        if failed:
            bad = endtoend.failed_in(records, t_open, t_close,
                                     mix["loop"] == "open")
            wrong.append(f"{failed} of {attempted} requests failed, e.g. "
                         + "; ".join(f"#{r.index} {r.status} {r.error}"
                                     for r in bad[:5]))
        # --trace 2: the same traffic runs on through the capture and its
        # stop; attempted and failed stay the window's
        late = endtoend.failed_in(records, t_close, float("inf"), False)
        if late and args.trace == 2:
            wrong.append(f"{len(late)} requests due after the window failed, "
                         "e.g. " + "; ".join(f"#{r.index} {r.status} {r.error}"
                                             for r in late[:5]))
        if restarts:
            wrong.append(f"the batcher restarted {restarts} time(s)")
        if slow:
            wrong.append(f"compiled under load for {SLOW_COMPILE_S}s or more: {slow}")
        correct = not wrong
        log(f"attempted {attempted}, failed {failed}, restarts {restarts}, "
            f"compiles >= {SLOW_COMPILE_S}s under load: {slow}, engine exit {rc}")
        for reason in wrong:
            print(f"benchmark: INCORRECT: {reason}", file=sys.stderr)
        if wrong:
            print(f"--- engine log tail ---\n{log_tail(child.log_path)}",
                  file=sys.stderr)
        if rc != 0:
            # the replica's exit code on SIGTERM is chip_smoke.py's to judge;
            # it says nothing of the outputs, so it is named and not judged
            print(f"benchmark: warning: the engine exited {rc} on SIGTERM",
                  file=sys.stderr)
        metrics: dict = {}
        if args.trace != 1:
            for m in e2e:
                value = setup_s if m["name"] == "setup_s" else endtoend.compute(
                    m["name"], records, t_open, t_close)
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
            log(f"samples: ttft {len(endtoend.ttft_ms(records, t_open, t_close))}, "
                f"tpot {len(endtoend.tpot_ms(records, t_open, t_close))}, tokens "
                f"{endtoend.tokens_in(records, t_open, t_close)}")
        if args.trace:
            run = {
                "cell": cell, "config": cfg, "architecture": engine.arch,
                "traffic": mix, "peaks": peaks,
                "records": records, "window": (t_open, t_close),
                "ready": ready, "counters": (snap_open, snap_close),
                "slo": slo, "trace": trace,
                "trace_window": trace_window, "trace_counters": trace_snaps,
                "steps_per_burst": snap_open["steps_per_burst"],
            }
            for m in manifest.metrics_of(man, "per_layer", cell["name"]):
                value = manifest.layer_reader(ROOT, man, m["name"])(run)
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["memory_peak_bytes"] = snap_end["memory_peak_bytes"]
        result = {"correct": correct, "attempted": attempted, "failed": failed,
                  "metrics": metrics, "device": device}
        if trace:
            device["busy_s"], device["window_s"] = trace["busy_s"], trace["window_s"]
            result["breakdown"] = {"device_ops": trace["device_ops"],
                                   "idle_gaps": trace["idle_gaps"]}
        if rehearse:
            print("rehearsal (cpu, tiny model; not a result): "
                  + json.dumps(result), flush=True)
        else:
            print(json.dumps(result), flush=True)
        return 0
    except (RunFailed, manifest.ManifestError, OSError, ValueError) as e:
        print(f"benchmark: FAILED: {e}\n--- engine log tail ---\n"
              f"{log_tail(child.log_path)}", file=sys.stderr)
        return 1
    finally:
        if load is not None:
            try:
                load.stop()
            except RuntimeError as e:
                print(f"benchmark: {e}", file=sys.stderr)
        child.stop()


if __name__ == "__main__":
    sys.exit(main())
