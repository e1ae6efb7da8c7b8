#!/usr/bin/env python3
"""Find an open-loop cell's knee, once: one engine, short windows at rising rates.

    python3 benchmark/sweep.py --workload <cell> --seed <n> --rates 6,9,12 --seconds 20

Not part of a run: the benchmark offers load at the rate fixed in the
traffic file and never searches for one. This is the tool that found that
rate; its table goes into PERF.md. The knee is the highest rate at which
the backlog does not grow through the window: time to first token of the
window's second half no worse than its first half's, and the requests in
flight at its end no more than in its middle.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from benchmark import client, endtoend, run  # noqa: E402


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--rates", required=True, help="req/s, comma-separated")
    parser.add_argument("--seconds", type=float, default=20.0)
    parser.add_argument("--rehearse-cpu", action="store_true")
    args = parser.parse_args(argv)
    man, cell, cfg, mix = run.load_cell(args.workload, args.rehearse_cpu)
    if mix["loop"] != "open":
        print("sweep: the cell's traffic is not an open loop", file=sys.stderr)
        return 2
    run_dir = run.next_run_dir(os.path.join(HERE, "_runs", cell["name"]), "sweep")
    engine = run.Engine(man, cell, cfg, mix, args.seed, args.rehearse_cpu, run_dir)
    rows = []
    try:
        for rate in (float(r) for r in args.rates.split(",")):
            load = client.Load(engine.port, dict(mix, rate_rps=rate), args.seed,
                               cfg["vocab_size"], cfg["server"]["slots"])
            load.start()
            time.sleep(float(mix["ramp_s"]))
            t0 = time.monotonic()
            time.sleep(args.seconds / 2)
            mid_in_flight = load.in_flight()
            time.sleep(args.seconds / 2)
            t1, end_in_flight = time.monotonic(), load.in_flight()
            time.sleep(float(mix.get("drain_s", 0)))
            load.stop()
            tm = (t0 + t1) / 2
            rec = load.records
            first, second = (endtoend.ttft_ms(rec, a, b) for a, b in ((t0, tm), (tm, t1)))
            attempted, failed = endtoend.failures(rec, t0, t1, True)
            row = {
                "rate_rps": rate, "attempted": attempted, "failed": failed,
                "tokens_per_s": endtoend.tokens_in(rec, t0, t1) / (t1 - t0),
                "ttft_p50_first_half_ms": endtoend.percentile(first, 50) if first else None,
                "ttft_p50_second_half_ms": endtoend.percentile(second, 50) if second else None,
                "ttft_p95_ms": endtoend.percentile(first + second, 95) if first + second else None,
                "tpot_p50_ms": endtoend.compute("tpot_p50_ms", rec, t0, t1)
                if endtoend.tpot_ms(rec, t0, t1) else None,
                "in_flight_mid": mid_in_flight, "in_flight_end": end_in_flight,
            }
            rows.append(row)
            print("sweep: " + json.dumps(row), flush=True)
            time.sleep(3.0)     # the lanes the stop freed come back
    finally:
        engine.child.stop()
    with open(os.path.join(run_dir, "sweep.json"), "w") as f:
        json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
