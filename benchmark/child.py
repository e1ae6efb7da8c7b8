"""The process that owns the chip: the program's own entry, plus a control line.

Runs ``seldon_core_tpu.engine_main`` unchanged on the main thread with the
predictor spec the parent wrote. A second thread answers one-line commands
from the parent on stdin with one JSON line each on stdout (the engine's
log goes to stderr):

    reference        the architecture module's ``compare_served``: the
                     served model against its plain reference
    snapshot         the batcher's counters, SLO samples since the last
                     snapshot, and the device's memory statistics
    trace_start DIR  the program's ``tracing.start_capture(DIR)``: the JAX
                     profiler (only this process can); the reply carries a
                     snapshot taken once it runs
    trace_stop       ``tracing.stop_capture()``; its report rides under
                     ``program``, as the program gave it. The snapshot is
                     taken before the stop, which can take many seconds to
                     write the trace

The first line on stdout names the device, before anything is built.
"""

from __future__ import annotations

import argparse
import gc
import importlib
import json
import os
import sys
import threading
import time

# the batcher's counters that some per-layer metric, or ``correct``, reads
COUNTERS = ("admitted", "tokens", "lane_steps", "prefill_tokens",
            "slo_samples", "batcher_restarts")


def say(obj: dict) -> None:
    sys.stdout.write(json.dumps(obj) + "\n")
    sys.stdout.flush()


def find_server():
    from seldon_core_tpu.servers.generateserver import GenerateServer

    for obj in gc.get_objects():
        if isinstance(obj, GenerateServer) and obj.batcher is not None:
            return obj
    return None


class Control:
    def __init__(self, seed: int, arch):
        self.seed = seed
        self.arch = arch
        self.slo_seen = 0

    def snapshot(self) -> dict:
        import jax

        server = find_server()
        if server is None:
            return {"error": "no loaded GenerateServer in this process"}
        batcher = server.batcher
        stats = {k: batcher.stats.get(k, 0) for k in COUNTERS}
        # samples of requests completed since the last snapshot, at full
        # resolution: (queue_wait_s, submit-anchored ttft_s, tpot_s | None)
        new = min(int(stats["slo_samples"]) - self.slo_seen,
                  len(batcher.slo_recent))
        samples = list(batcher.slo_recent)[-new:] if new > 0 else []
        self.slo_seen = int(stats["slo_samples"])
        memory = [d.memory_stats() or {} for d in jax.local_devices()]
        return {
            "t": time.monotonic(), "stats": stats, "slo": samples,
            "steps_per_burst": batcher._k,
            "memory_peak_bytes": max(
                (m.get("peak_bytes_in_use", 0) for m in memory), default=0),
        }

    def reference(self) -> dict:
        server = find_server()
        if server is None:
            return {"error": "no loaded GenerateServer in this process"}
        t0 = time.monotonic()
        out = self.arch.compare_served(server._model, server.batcher.params,
                                       self.seed)
        out["seconds"] = time.monotonic() - t0
        return out

    def serve(self) -> None:
        from seldon_core_tpu import tracing

        for line in sys.stdin:
            words = line.split()
            if not words:
                continue
            try:
                if words[0] == "snapshot":
                    say(self.snapshot())
                elif words[0] == "reference":
                    say(self.reference())
                elif words[0] == "trace_start":
                    tracing.start_capture(words[1])
                    say(self.snapshot())
                elif words[0] == "trace_stop":
                    snap = self.snapshot()
                    program = tracing.stop_capture()
                    say(dict(snap, stop_s=time.monotonic() - snap["t"],
                             program=program))
                else:
                    say({"error": f"unknown command {words[0]!r}"})
            except Exception as e:  # noqa: BLE001 - the parent decides
                say({"error": f"{type(e).__name__}: {e}"[:2000]})


def main(argv=None) -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--spec", required=True)
    parser.add_argument("--http-port", required=True)
    parser.add_argument("--chips", type=int, required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--cache-dir", required=True)
    parser.add_argument("--architecture", required=True)
    args = parser.parse_args(argv)

    import jax

    # every executable, however quick to compile: a warm run compiles nothing
    jax.config.update("jax_compilation_cache_dir", args.cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    try:
        devices = jax.devices()  # JAX_PLATFORMS=tpu: raises when there is none
    except RuntimeError as e:
        print(f"benchmark child: JAX found no accelerator: {e}", file=sys.stderr)
        return 3
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    if os.environ.get("JAX_PLATFORMS") != "cpu" and device["platform"] != "tpu":
        print(f"benchmark child: JAX runs on {device['platform']!r}, not a TPU",
              file=sys.stderr)
        return 3
    if len(devices) < args.chips:
        print(f"benchmark child: {len(devices)} chip(s), the cell needs "
              f"{args.chips}", file=sys.stderr)
        return 3
    say({"device": device})

    # by the dotted name the parent found it under (``manifest.architecture``),
    # and nothing else before the engine starts: what this process does here
    # shows in ``warm_s`` (PERF.md, PR 27)
    arch = importlib.import_module(args.architecture)
    arch.register()
    control = threading.Thread(target=Control(args.seed, arch).serve,
                               daemon=True)
    control.start()

    from seldon_core_tpu import engine_main

    engine_main.main(["--spec", args.spec, "--host", "127.0.0.1",
                      "--http-port", args.http_port, "--no-grpc"])
    # the parent closes the control line before it sends SIGTERM; a reader
    # still blocked on stdin when the interpreter finalises can abort it
    control.join(timeout=5.0)
    return 0


if __name__ == "__main__":
    sys.exit(main())
