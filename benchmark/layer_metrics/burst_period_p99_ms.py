"""Burst cadence over the window: the 99th percentile of one burst's
dispatch to the next's (``dispatched_t``), each poll row that dispatched a
burst against the last one before it that did (a row between them that only
admitted or read lies inside the period). A first token waits for the bursts
ahead of its own, so the tail of the period is the tail of TTFT. A stretch
that waited idle for a request begins a new run of bursts: the period across
it is dropped. The counts of periods and of those dropped go to stderr."""
import sys

from benchmark import endtoend, polls


def read(run):
    rows = polls.in_window(run)
    if not rows:
        return None
    periods, dropped, last, idled = [], 0, None, False
    for row in rows:
        idled = idled or "idle" in row["phase_s"]
        if "dispatched_t" not in row:
            continue
        if last is not None and idled:
            dropped += 1
        elif last is not None:
            periods.append(1e3 * (row["dispatched_t"] - last))
        last, idled = row["dispatched_t"], False
    if not periods:
        return None
    print(f"benchmark: burst_period_p99_ms over {len(periods)} periods "
          f"({dropped} across an idle wait dropped), "
          f"median {endtoend.percentile(periods, 50):.2f} ms", file=sys.stderr)
    return endtoend.percentile(periods, 99)
