#!/usr/bin/env python3
"""CI smoke for pod-scale sharded generate serving.

Forces an 8-device host-platform mesh (the CPU stand-in for a pod
slice), boots one tiny checkpoint twice behind real engines on
sockets — an unmeshed 1-device server and a ``mesh_shape`` server with
mesh-sharded params + sharded KV cache — then asserts:

* greedy AND seeded-sampling responses through the sharded engine are
  byte-identical to the 1-device server's (serving math is
  sharded-storage / replicated-compute, so the mesh must never change
  an output byte), across plain decode, a shared-prefix repeat and a
  chunked long-prompt admission;
* the ``seldon.io/mesh`` annotation round-trips through a predictor
  spec into the same mesh the knob builds, and a malformed shape is
  refused at admission;
* the ``seldon_engine_mesh_*`` series (devices / data / model /
  param_shard_bytes / kv_shard) are present in the Prometheus
  exposition with the right values, and the unmeshed engine publishes
  none of them.

Run directly (``JAX_PLATFORMS=cpu python tools/sharded_smoke.py``) or
from the CI sharded_smoke step. Exits non-zero on any failure.
"""

from __future__ import annotations

import json
import os
import sys
import tempfile


def main() -> int:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    # the pod-slice stand-in: 8 host devices, set BEFORE jax imports
    os.environ.setdefault(
        "XLA_FLAGS", "--xla_force_host_platform_device_count=8"
    )
    # runtime thread-role assertions (analysis/roles.py) fail the smoke
    # loudly on a scheduler-thread violation (must precede seldon imports)
    os.environ.setdefault("SELDON_DEBUG_THREADS", "1")
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    import http.client

    from seldon_core_tpu.graph.engine_metrics import REGISTRY
    from seldon_core_tpu.graph.spec import GraphSpecError, PredictorSpec
    from seldon_core_tpu.parallel.mesh import MeshShapeError
    from seldon_core_tpu.servers.generateserver import GenerateServer
    from seldon_core_tpu.testing import EngineHarness, write_model_dir

    failures = []

    def check(name: str, ok: bool, detail: str = ""):
        print(f"{'ok  ' if ok else 'FAIL'} {name}" + (f": {detail}" if detail else ""))
        if not ok:
            failures.append(name)

    mesh_shape = "data=2,model=4"
    with tempfile.TemporaryDirectory(prefix="sharded-smoke-") as root:
        cfg = {"vocab_size": 256, "d_model": 32, "n_layers": 2, "n_heads": 4,
               "n_kv_heads": 4, "d_ff": 64, "max_seq": 64}
        model_dir = write_model_dir(root, "llm", cfg)
        common = dict(model_uri=model_dir, slots=2, steps_per_poll=2,
                      warmup_prompt_lens=[4], warmup_max_new_tokens=8,
                      prefix_cache_hbm_bytes=1 << 20,
                      prefix_cache_min_tokens=8)

        plain = GenerateServer(**common)
        plain.load()
        shard = GenerateServer(mesh_shape=mesh_shape, prefill_chunk=8,
                               **common)
        shard.load()

        plain_h = EngineHarness(plain, name="plain").start()
        shard_h = EngineHarness(shard, name="sharded").start()
        headers = {"Content-Type": "application/json"}

        def gen(port: int, prompt, temperature=0.0, seed=0) -> dict:
            conn = http.client.HTTPConnection("127.0.0.1", port)
            conn.request("POST", "/api/v0.1/predictions", json.dumps({
                "jsonData": {"prompt_tokens": [prompt], "max_new_tokens": 8,
                             "temperature": temperature, "seed": seed},
            }).encode(), headers)
            resp = conn.getresponse()
            payload = resp.read()
            conn.close()
            if resp.status != 200:
                raise RuntimeError(f"HTTP {resp.status}: {payload[:160]!r}")
            return json.loads(payload)["jsonData"]

        try:
            # -- the mesh the knob built ----------------------------------
            mesh = shard.batcher.mesh
            check("sharded server serves on the requested mesh",
                  mesh is not None and dict(mesh.shape) ==
                  {"data": 2, "model": 4},
                  f"mesh={None if mesh is None else dict(mesh.shape)}")

            # -- byte identity: 1-device vs 8-device mesh -----------------
            prompts = [[5, 6, 7, 8], [9, 10, 11], [1, 2, 3, 4, 5, 6]]
            for p in prompts:
                ref = gen(plain_h.http_port, p)["tokens"][0]
                got = gen(shard_h.http_port, p)["tokens"][0]
                check(f"greedy identical (len {len(p)})", got == ref,
                      "" if got == ref else f"{got} != {ref}")
            for i, p in enumerate(prompts):
                ref = gen(plain_h.http_port, p, 0.8, 17 + i)["tokens"][0]
                got = gen(shard_h.http_port, p, 0.8, 17 + i)["tokens"][0]
                check(f"seeded identical (len {len(p)})", got == ref,
                      "" if got == ref else f"{got} != {ref}")

            # shared-prefix repeat: the second admission splices the radix
            # prefix into the SHARDED cache and must not change a byte
            system = list(range(20, 32))
            _ = gen(shard_h.http_port, system + [40, 41])
            ref = gen(plain_h.http_port, system + [50, 51])["tokens"][0]
            got = gen(shard_h.http_port, system + [50, 51])
            check("shared-prefix greedy identical", got["tokens"][0] == ref)
            check("prefix splice actually hit",
                  (got.get("cache_hit_tokens") or [0])[0] >= 8,
                  f"hits={(got.get('cache_hit_tokens') or [0])[0]}")

            # chunked long-prompt admission through the sharded staging slab
            long_p = [(i * 7 + 3) % 61 for i in range(30)]
            ref = gen(plain_h.http_port, long_p)["tokens"][0]
            got = gen(shard_h.http_port, long_p)["tokens"][0]
            check("chunked-prefill greedy identical", got == ref,
                  "" if got == ref else f"{got} != {ref}")

            # -- seldon.io/mesh annotation: round-trip + refusal ----------
            from seldon_core_tpu.graph.spec import parse_mesh_annotation

            spec = PredictorSpec.from_dict({
                "name": "p", "graph": {"name": "m", "type": "MODEL",
                                       "implementation": "GENERATE_SERVER"},
                "annotations": {"seldon.io/mesh": mesh_shape},
            })
            check("seldon.io/mesh annotation parses to the knob's shape",
                  parse_mesh_annotation(spec) == {"data": 2, "model": 4})
            try:
                parse_mesh_annotation(PredictorSpec.from_dict({
                    "name": "p", "graph": {
                        "name": "m", "type": "MODEL",
                        "implementation": "GENERATE_SERVER"},
                    "annotations": {"seldon.io/mesh": "data=2,model=nope"},
                }))
                check("malformed seldon.io/mesh refused", False)
            except (GraphSpecError, MeshShapeError):
                check("malformed seldon.io/mesh refused", True)

            # -- the seldon_engine_mesh_* exposition ----------------------
            expo = REGISTRY.expose()
            for series in ("seldon_engine_mesh_devices",
                           "seldon_engine_mesh_data",
                           "seldon_engine_mesh_model",
                           "seldon_engine_mesh_param_shard_bytes",
                           "seldon_engine_mesh_kv_shard"):
                check(f"exposition has {series}", series in expo)
            gauges = {d["key"]: d["value"] for d in shard.metrics()}
            check("mesh gauges carry the served shape",
                  gauges.get("gen_mesh_devices") == 8
                  and gauges.get("gen_mesh_data") == 2
                  and gauges.get("gen_mesh_model") == 4
                  and gauges.get("gen_mesh_kv_shard") == 4,
                  f"gauges={ {k: v for k, v in gauges.items() if 'mesh' in k} }")
            check("per-shard param bytes strictly under the full residency",
                  0 < gauges.get("gen_mesh_param_shard_bytes", 0)
                  < shard._model.n_params() * 4)
            plain_gauges = {d["key"] for d in plain.metrics()}
            check("unmeshed engine publishes no mesh gauges",
                  not any(k.startswith("gen_mesh_") for k in plain_gauges))
        finally:
            plain_h.stop()
            shard_h.stop()
            plain.close()
            shard.close()

    if failures:
        print(f"\nsharded smoke FAILED: {failures}", file=sys.stderr)
        return 1
    print("\nsharded smoke passed")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
