#!/usr/bin/env python3
"""Lay the flight recorder's poll rows on the profiler's trace of one run.

    python3 tools/poll_overlay.py benchmark/_runs/<cell>/<run> [--json]

Reads what ``benchmark/run.py --trace 2`` (or ``1``) keeps in the run's
directory: ``capture.json`` (the program's ``tracing.stop_capture()`` report:
``polls``, ``t0``/``t1``) and ``trace_events.json.gz`` (the trace
as ``benchmark/trace.py:extract`` read it). The trace's times count from
the profiler session's start; the host span ``capture.clock
monotonic_s=<reading>`` that ``tracing.start_capture`` opens every profiled
capture with says what ``time.monotonic()`` read where it begins, so a row's
stretch ``[t, t + sum(phase_s)]`` has a place on the trace. Two checks, over
the rows that lie wholly between that span and the capture's end:

**spans**   each row against the scheduler thread's own ``batcher.<phase>``
            annotations of the same poll (those whose midpoint lies in the
            row's stretch): how far the row's start and end lie from the
            first and last of them, and how far each phase's seconds lie
            from the annotations' durations summed.
**drained** each row with ``drained: true`` against the device's ops: the
            sample is taken where the row's first annotation that
            dispatches begins (``admit`` where it admitted, else ``chunks``
            or ``dispatch``), and the device should be in a gap of 20 us or
            more there, which ends at the next device op. A row with
            ``drained: false`` should find the device busy, or about to be.

A time here is the host's and the device's as the profiler stamped them; the
tool computes nothing on a CPU trace that it would call a device number.
"""

from __future__ import annotations

import bisect
import gzip
import json
import os
import sys

GAP_S = 20e-6
PREFIX = "batcher."
MARK = "capture.clock monotonic_s="     # tracing.CAPTURE_CLOCK_SPAN


def load(run_dir: str) -> tuple:
    with open(os.path.join(run_dir, "capture.json")) as f:
        report = json.load(f)
    with gzip.open(os.path.join(run_dir, "trace_events.json.gz"), "rt") as f:
        events = json.load(f)
    return report, events


def rows_inside(report: dict, host: list) -> list:
    """The poll rows whose whole stretch lies inside what the profiler
    recorded, from the ``capture.clock`` span to the report's ``t1``, each
    with its ``end``, on the trace's clock. (``t0`` is read before the
    profiler starts; the row in progress while it does holds the thread's
    wait for it, and its phase's span twice: ``PhaseClock.reenter``.)"""
    mark = next(((name, s) for name, s, _d in host if name.startswith(MARK)),
                None)
    if mark is None:
        raise SystemExit("the trace holds no capture.clock span: a program "
                         "from before it cannot be laid on its trace")
    began = float(mark[0][len(MARK):])
    offset = mark[1] - began
    out = []
    for row in report.get("polls") or []:
        if row.get("type") != "poll":
            continue
        end = row["t"] + sum(row["phase_s"].values())
        if began <= row["t"] and end <= report["t1"]:
            out.append(dict(row, t=row["t"] + offset, end=end + offset))
    return out


def spans(rows: list, host: list) -> dict:
    """Rows against the ``batcher.<phase>`` annotations. Returns the worst
    and the median distance in ms, and gives each row its annotations
    (``row["spans"]``, by start)."""
    notes = sorted((s, d, name[len(PREFIX):]) for name, s, d in host
                   if name.startswith(PREFIX))
    starts = [r["t"] for r in rows]
    for row in rows:
        row["spans"] = []
    for s, d, phase in notes:
        i = bisect.bisect_right(starts, s + d / 2) - 1
        if i >= 0 and s + d / 2 < rows[i]["end"]:
            rows[i]["spans"].append((s, d, phase))
    edge, phase_off, bare, worst = [], [], 0, None
    for row in rows:
        if not row["spans"]:
            bare += 1
            continue
        first, last = row["spans"][0], row["spans"][-1]
        edge.append(max(abs(first[0] - row["t"]),
                        abs(last[0] + last[1] - row["end"])))
        by_phase: dict = {}
        for _s, d, phase in row["spans"]:
            by_phase[phase] = by_phase.get(phase, 0.0) + d
        phase_off.append(max(
            abs(by_phase.get(p, 0.0) - row["phase_s"].get(p, 0.0))
            for p in set(by_phase) | set(row["phase_s"])))
        if phase_off[-1] == max(phase_off):
            worst = {"poll": row["poll"], "row_ms": {
                p: round(1e3 * v, 3) for p, v in row["phase_s"].items()},
                "annotations_ms": {
                    p: round(1e3 * v, 3) for p, v in by_phase.items()}}

    def ms(values, q):
        v = sorted(values)
        return 1e3 * v[min(len(v) - 1, int(q * len(v)))] if v else None

    return {"rows": len(rows), "rows_without_annotations": bare,
            "annotations": len(notes),
            "edge_ms": {"median": ms(edge, 0.5), "max": ms(edge, 1.0)},
            "phase_ms": {"median": ms(phase_off, 0.5), "max": ms(phase_off, 1.0),
                         "worst_row": worst}}


def drained(rows: list, devices: list) -> dict:
    """``drained`` against the device's ops (after :func:`spans`)."""
    busy: list = []
    for dev in devices:
        for _n, s, d, _t in dev["ops"]:
            busy.append([s, s + d])
    busy.sort()
    union: list = []
    for s, e in busy:
        if union and s <= union[-1][1]:
            union[-1][1] = max(union[-1][1], e)
        else:
            union.append([s, e])
    begins = [s for s, _e in union]
    out = {"true": 0, "true_in_gap": 0, "false": 0, "false_busy": 0,
           "false_idle_under_gap": 0, "outside_device_trace": 0,
           "exceptions": []}
    for row in rows:
        if not row.get("spans"):
            continue
        wanted = ("admit",) if row.get("admitted") else ("chunks", "dispatch")
        sample = next((s for s, _d, p in row["spans"] if p in wanted), None)
        if sample is None:
            continue
        i = bisect.bisect_right(begins, sample) - 1
        if i < 0 or (i + 1 == len(union) and sample >= union[i][1]):
            # the device's plane starts later and ends earlier than the host's
            out["outside_device_trace"] += 1
            continue
        inside = sample < union[i][1]
        # the gap the sample lies in, which ends at the next device op
        gap = None if inside else union[i + 1][0] - union[i][1]
        if row["drained"]:
            out["true"] += 1
            if gap is not None and gap >= GAP_S:
                out["true_in_gap"] += 1
            else:
                out["exceptions"].append({
                    "poll": row["poll"], "sample": sample, "device_busy": inside,
                    "gap_us": None if gap is None else gap * 1e6})
        else:
            out["false"] += 1
            if inside:
                out["false_busy"] += 1
            elif gap is not None and gap < GAP_S:
                out["false_idle_under_gap"] += 1
            else:
                out["exceptions"].append({
                    "poll": row["poll"], "drained": False, "sample": sample,
                    "gap_us": None if gap is None else gap * 1e6,
                    # an idle device the loop called busy: the newest
                    # program finished between the loop's look and here
                    "idle_before_sample_us": (sample - union[i][1]) * 1e6})
    return out


def overlay(run_dir: str) -> dict:
    report, events = load(run_dir)
    rows = rows_inside(report, events["host"])
    return {"run": run_dir, "spans": spans(rows, events["host"]),
            "drained": drained(rows, events["devices"])}


def main(argv: list) -> int:
    args = [a for a in argv[1:] if a != "--json"]
    if len(args) != 1:
        print(__doc__, file=sys.stderr)
        return 2
    out = overlay(args[0])
    if "--json" in argv:
        print(json.dumps(out))
        return 0
    s, d = out["spans"], out["drained"]
    print(f"{out['run']}: {s['rows']} rows inside the capture, "
          f"{s['annotations']} batcher.* annotations "
          f"({s['rows_without_annotations']} rows found none)")
    print(f"  row edges against their first and last annotation: median "
          f"{s['edge_ms']['median']} ms, max {s['edge_ms']['max']} ms")
    print(f"  a phase's seconds against its annotations summed: median "
          f"{s['phase_ms']['median']} ms, max {s['phase_ms']['max']} ms "
          f"(in {s['phase_ms']['worst_row']})")
    print(f"  drained true: {d['true']} rows, {d['true_in_gap']} in a device "
          f"gap of {GAP_S * 1e6:.0f} us or more that ends at the next op")
    print(f"  drained false: {d['false']} rows, {d['false_busy']} with the "
          f"device busy, {d['false_idle_under_gap']} in a gap under "
          f"{GAP_S * 1e6:.0f} us; {d['outside_device_trace']} rows sampled "
          "outside the device's trace, not judged")
    for e in d["exceptions"]:
        print(f"  exception: {e}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
