#!/usr/bin/env python
"""Headline benchmark: engine REST predictions throughput, stub model.

Mirrors the reference's published benchmark — locust hammering the engine's
``/api/v0.1/predictions`` with the in-engine SIMPLE_MODEL stub, measuring
orchestrator + serialization overhead (reference:
doc/source/reference/benchmarking.md:33-44 — 12,088.95 req/s on a GCP
n1-standard-16 with a 3-node / 64-worker locust cluster;
notebooks/benchmark_simple_model.ipynb). Here the native C++ engine and the
load generator share ONE core of the TPU-VM host: the printed
``vs_baseline`` is against the reference's 16-core number anyway.

On top of the stub headline, a MODEL TIER measures the north-star metric
on the local chip (BASELINE.json): ResNet-50 over engine REST (raw uint8),
BERT-base over engine gRPC (binary int32 raw), and DecoderLM generate()
through the continuous batcher — req/s/chip, rows/s, p50/p99 and MFU via
seldon_core_tpu.modelbench. Results are also written into
BASELINE.json["published"]. Set BENCH_MODELS=0 to skip the model tier,
BENCH_MODEL_SECONDS to change the per-model measure window.

Output contract (the harness parses the FINAL stdout line, and long
captures keep only the tail — a multi-KB line gets its head cut and
parses as nothing):

  1. a human-readable indented dump of the full results dict,
  2. the full results dict as one JSON line (for local tooling),
  3. LAST: a compact one-line JSON summary ({"compact": true, ...})
     small enough to survive tail-truncated captures intact.

``tools/gen_arch_numbers.py`` understands the compact line and prefers
the full line / BASELINE.json["published"] for the numbers table.
"""

from __future__ import annotations

import json
import os
import socket
import subprocess
import sys

REFERENCE_REST_RPS = 12088.95  # reference benchmarking.md:33-44
REFERENCE_GRPC_RPS = 28256.39  # reference benchmarking.md:52-58 (binary path)


def free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def run_model_tier(repo: str) -> dict:
    """North-star model-level numbers. A failure in the tier is fatal: the
    exception propagates and the benchmark exits non-zero."""
    from seldon_core_tpu import modelbench

    seconds = float(os.environ.get("BENCH_MODEL_SECONDS", 8.0))
    tiny = os.environ.get("BENCH_TINY", "") == "1"
    results = modelbench.run_model_tier(seconds=seconds, tiny=tiny)
    if tiny:
        # smoke-test mode: never overwrite the published chip numbers
        results["tiny"] = True
        return results
    device = results.get("device", {})
    if device.get("platform") != "tpu":
        # a CPU timing is not a model-tier number: refuse, don't report
        raise SystemExit(
            f"bench: the model tier ran on {device.get('platform')!r} "
            f"({device.get('device_kind')!r}), not a TPU; set BENCH_TINY=1 "
            "for the CPU smoke or BENCH_MODELS=0 to skip the tier"
        )
    try:
        path = os.path.join(repo, "BASELINE.json")
        with open(path) as f:
            baseline = json.load(f)
        baseline["published"] = results
        with open(path, "w") as f:
            json.dump(baseline, f, indent=2)
    except Exception as e:  # noqa: BLE001
        results["publish_error"] = str(e)
    return results


def main() -> None:
    repo = os.path.dirname(os.path.abspath(__file__))
    sys.path.insert(0, repo)
    from seldon_core_tpu.native_engine import BIN_PATH, build

    build()
    clients = int(os.environ.get("BENCH_CLIENTS", 32))
    seconds = float(os.environ.get("BENCH_SECONDS", 5.0))
    port = free_port()
    out = subprocess.run(
        [
            BIN_PATH, "--port", str(port), "--bench",
            "--clients", str(clients), "--seconds", str(seconds),
        ],
        check=True, capture_output=True, text=True,
    )
    stats = json.loads(out.stdout.strip().splitlines()[-1])
    if stats.get("errors"):
        raise SystemExit(f"bench had {stats['errors']} errors: {stats}")
    # binary protobuf front (raw tensors, no JSON/base64) vs the
    # reference's binary path headline (gRPC, benchmarking.md:52-58)
    port_b = free_port()
    out_b = subprocess.run(
        [
            BIN_PATH, "--port", str(port_b), "--bench-binary",
            "--clients", str(clients), "--seconds", str(seconds),
        ],
        check=True, capture_output=True, text=True,
    )
    stats_b = json.loads(out_b.stdout.strip().splitlines()[-1])
    if stats_b.get("errors"):
        raise SystemExit(f"binary bench had {stats_b['errors']} errors: {stats_b}")
    # native gRPC front (hand-rolled h2c + HPACK) vs the reference's gRPC
    # headline — apples-to-apples transport this time, driven by the
    # in-binary h2 load generator (a python grpcio client tops out ~8.6k
    # req/s on this host and would measure the client, not the server)
    port_g = free_port()
    gport = free_port()
    out_g = subprocess.run(
        [
            BIN_PATH, "--port", str(port_g), "--grpc-port", str(gport),
            "--bench-grpc", "--clients", str(min(clients, 8)),
            "--seconds", str(seconds),
        ],
        check=True, capture_output=True, text=True,
    )
    stats_g = json.loads(out_g.stdout.strip().splitlines()[-1])
    if stats_g.get("errors"):
        raise SystemExit(f"grpc bench had {stats_g['errors']} errors: {stats_g}")
    result = {
        "metric": "engine REST predictions throughput (stub model, 1 core)",
        "value": round(stats["rps"], 2),
        "unit": "req/s",
        "vs_baseline": round(stats["rps"] / REFERENCE_REST_RPS, 3),
        "p50_ms": stats["p50_ms"],
        "p99_ms": stats["p99_ms"],
        "requests": stats["requests"],
        "baseline": REFERENCE_REST_RPS,
        "baseline_source": "reference doc/source/reference/benchmarking.md:33-44 (n1-standard-16)",
        "binary_front": {
            "value": round(stats_b["rps"], 2),
            "unit": "req/s",
            "vs_grpc_baseline": round(stats_b["rps"] / REFERENCE_GRPC_RPS, 3),
            "p50_ms": stats_b["p50_ms"],
            "p99_ms": stats_b["p99_ms"],
            "transport": "binary protobuf REST (raw tensors)",
            "baseline": REFERENCE_GRPC_RPS,
            "baseline_source": "reference benchmarking.md:52-58 (gRPC, n1-standard-16)",
        },
        "grpc_front": {
            "value": round(stats_g["req_per_s"], 2),
            "unit": "req/s",
            "vs_grpc_baseline": round(stats_g["req_per_s"] / REFERENCE_GRPC_RPS, 3),
            "p50_ms": stats_g["p50_ms"],
            "p99_ms": stats_g["p99_ms"],
            "transport": "native gRPC (hand-rolled h2c + HPACK, 64 streams/conn)",
            "baseline": REFERENCE_GRPC_RPS,
            "baseline_source": "reference benchmarking.md:52-58 (gRPC, n1-standard-16)",
        },
    }
    if os.environ.get("BENCH_MODELS", "1") != "0":
        result["model_tier"] = run_model_tier(repo)
    # the front headlines live in an ARTIFACT, not just this process's
    # stdout tail: the driver keeps only the tail of long captures, and
    # round 4's most-quoted number (native gRPC req/s) survived nowhere
    # but prose. Same publish guard as the model tier: only a full
    # benchmark-host capture (model tier ran, on TPU, not tiny) may
    # overwrite the published headline numbers. captured_at stamps both
    # blocks so gen_arch_numbers can prove same-capture provenance.
    mt = result.get("model_tier") or {}
    publishable = (
        mt.get("device", {}).get("platform") == "tpu"
        and not mt.get("tiny")
    )
    if publishable:
        try:
            import time as _time

            stamp = _time.time()
            path = os.path.join(repo, "BASELINE.json")
            with open(path) as f:
                baseline = json.load(f)
            if isinstance(baseline.get("published"), dict):
                baseline["published"]["captured_at"] = stamp
            baseline["published_fronts"] = {
                "captured_at": stamp,
                "stub_rest": {
                    "value": result["value"], "unit": "req/s",
                    "vs_baseline": result["vs_baseline"],
                    "p50_ms": result["p50_ms"], "p99_ms": result["p99_ms"],
                },
                "binary_front": result["binary_front"],
                "grpc_front": result["grpc_front"],
            }
            with open(path, "w") as f:
                json.dump(baseline, f, indent=2)
        except Exception as e:  # noqa: BLE001 - publishing never kills the run
            result["front_publish_error"] = str(e)
    # human-readable dump first, full single-line JSON next, and a COMPACT
    # single-line summary LAST: the driver stores only the tail of long
    # captures and parses the final line, so the final line must stay
    # small enough (~<1.5KB) to survive truncation intact
    print("=== bench results (full) ===")
    print(json.dumps(result, indent=2))
    print("=== machine-readable ===")
    print(json.dumps(result))
    print(json.dumps(compact_summary(result)))


def compact_summary(result: dict) -> dict:
    """Slim the results dict to headline numbers so the final stdout line
    parses even out of a tail-truncated capture."""
    out = {
        "compact": True,
        "metric": "engine REST predictions throughput (stub model, 1 core)",
        "value": result.get("value"),
        "unit": result.get("unit"),
        "vs_baseline": result.get("vs_baseline"),
    }
    for front in ("binary_front", "grpc_front"):
        f = result.get(front) or {}
        if f:
            out[front] = {"value": f.get("value"),
                          "vs_grpc_baseline": f.get("vs_grpc_baseline")}
    mt = result.get("model_tier") or {}
    tiers = {}
    for key, tier in mt.items():
        if not isinstance(tier, dict) or key == "device":
            continue
        slim = {}
        for field in ("tokens_per_s", "rows_per_s", "p50_ms", "mbu_pct",
                      "mfu_pct", "speedup_tokens_per_s", "greedy_identical"):
            if tier.get(field) is not None:
                slim[field] = tier[field]
        if slim:
            tiers[key] = slim
    out["model_tier"] = tiers
    # belt-and-braces: if a fat tier pushes the line past the tail-capture
    # budget, drop per-tier detail down to the single headline number
    if len(json.dumps(out)) > 1500:
        out["model_tier"] = {
            k: v.get("tokens_per_s", v.get("rows_per_s"))
            for k, v in tiers.items()
        }
    return out


if __name__ == "__main__":
    main()
