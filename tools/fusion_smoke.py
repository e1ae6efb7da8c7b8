#!/usr/bin/env python3
"""CI smoke for graph fusion + the RAG workload.

Boots TWO engines over the SAME loaded components on real sockets — one
with ``seldon.io/fuse: "true"``, one without — serving the RAG graph
(embed -> retrieve -> rerank -> RAG_PROMPT_BUILDER -> generate), then
asserts the whole fusion surface is live:

* fused and unfused responses are byte-identical (token output, tags,
  requestPath; wall-clock TIMER telemetry excluded) — the greedy
  generate tail included;
* the fused engine's ``/metrics`` exposes
  ``seldon_engine_fused_segments`` with dispatches counted (and no
  ``seldon_engine_fusion_fallbacks`` on the clean path);
* ``/flightrecorder`` carries the ``(fusion)`` pseudo-unit dump with
  ``fused_dispatch`` records, and ``tools/flight_report.py`` renders it
  (with the fallback-rate DIAGNOSIS when fallbacks dominate);
* a faulted engine (fault injector on the interior rerank unit) serves
  identical output per-unit with the fallback COUNTED in
  ``seldon_engine_fusion_fallbacks{reason="faults"}``.

Run directly (``JAX_PLATFORMS=cpu python tools/fusion_smoke.py``) or
from the CI fusion step. Exits non-zero on any failed check.
"""

from __future__ import annotations

import json
import os
import sys
import tempfile


def _scrub(payload: dict) -> dict:
    payload = json.loads(json.dumps(payload))
    meta = payload.get("meta") or {}
    meta.pop("puid", None)
    if "metrics" in meta:
        from seldon_core_tpu.servers.generateserver import COMPILE_TELEMETRY_KEYS

        # wall time is not data, nor is what XLA compiled when: a shape's
        # first call compiles, whichever engine makes it
        meta["metrics"] = [
            m for m in meta["metrics"]
            if m.get("type") != "TIMER"
            and m.get("key") not in COMPILE_TELEMETRY_KEYS
        ]
    return payload


def main() -> int:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    import http.client

    import numpy as np

    from seldon_core_tpu.graph.units import RagPromptBuilder
    from seldon_core_tpu.resilience.faults import FaultInjector
    from seldon_core_tpu.servers.generateserver import GenerateServer
    from seldon_core_tpu.servers.jaxserver import JAXServer
    from seldon_core_tpu.testing import EngineHarness, write_model_dir

    failures = []

    def check(name: str, ok: bool, detail: str = ""):
        print(f"{'ok  ' if ok else 'FAIL'} {name}"
              + (f": {detail}" if detail else ""))
        if not ok:
            failures.append(name)

    E, K, L, V = 16, 4, 6, 256
    with tempfile.TemporaryDirectory(prefix="fusion-smoke-") as root:
        bert_dir = write_model_dir(root, "bert", {
            "vocab_size": V, "d_model": 32, "n_layers": 2, "n_heads": 2,
            "d_ff": 64, "max_seq": 32, "num_classes": E,
        })
        ret_cfg = {"corpus_size": 64, "d_embed": E, "top_k": K,
                   "doc_len": L, "vocab_size": V, "seed": 7}
        ret_dir = write_model_dir(root, "retrieval", ret_cfg)
        rer_dir = write_model_dir(root, "reranker", ret_cfg)
        llm_dir = write_model_dir(root, "llm", {
            "vocab_size": V, "d_model": 32, "n_layers": 2, "n_heads": 2,
            "n_kv_heads": 2, "d_ff": 64, "max_seq": 32,
        })
        embed = JAXServer(model_uri=bert_dir)
        embed.load()
        retrieve = JAXServer(model_uri=ret_dir)
        retrieve.load()
        rerank = JAXServer(model_uri=rer_dir)
        rerank.load()
        gen = GenerateServer(model_uri=llm_dir, slots=2, steps_per_poll=1,
                             warmup_prompt_lens=[L],
                             warmup_max_new_tokens=8)
        gen.load()
        registry = {
            "embed": embed, "retrieve": retrieve, "rerank": rerank,
            "prompt": RagPromptBuilder(max_new_tokens=8), "generate": gen,
        }
        graph = {
            "name": "embed", "type": "MODEL", "children": [{
                "name": "retrieve", "type": "MODEL", "children": [{
                    "name": "rerank", "type": "MODEL", "children": [{
                        "name": "prompt",
                        "implementation": "RAG_PROMPT_BUILDER",
                        "children": [
                            {"name": "generate", "type": "MODEL"}
                        ],
                    }],
                }],
            }],
        }

        from seldon_core_tpu.graph.engine_metrics import MetricsRegistry

        def boot(name, fuse, faults=None):
            return EngineHarness(
                name=name, graph=json.loads(json.dumps(graph)),
                registry=registry, metrics=MetricsRegistry(),
                annotations={"seldon.io/fuse": "true"} if fuse else None,
                faults=faults,
            ).start()

        plain = boot("rag-plain", fuse=False)
        fused = boot("rag-fused", fuse=True)
        chaos = boot(
            "rag-chaos", fuse=True,
            faults=FaultInjector([{"unit": "rerank", "latency_ms": 1.0}]),
        )
        try:
            rs = np.random.RandomState(5)
            reqs = [
                {"data": {"ndarray": rs.randint(1, V, (1, 8)).tolist()}}
                for _ in range(4)
            ]

            def predict(harness, req):
                conn = http.client.HTTPConnection(
                    "127.0.0.1", harness.http_port, timeout=60
                )
                conn.request(
                    "POST", "/api/v0.1/predictions",
                    json.dumps(req).encode(),
                    {"Content-Type": "application/json"},
                )
                resp = conn.getresponse()
                payload = resp.read()
                if resp.status != 200:
                    raise RuntimeError(
                        f"predict {resp.status}: {payload[:200]!r}"
                    )
                return json.loads(payload)

            plain_outs = [_scrub(predict(plain, r)) for r in reqs]
            fused_outs = [_scrub(predict(fused, r)) for r in reqs]
            check("fused == unfused (greedy tail incl.)",
                  plain_outs == fused_outs)
            check(
                "requestPath covers every stage",
                list(fused_outs[0]["meta"]["requestPath"]) == [
                    "embed", "retrieve", "rerank", "prompt", "generate",
                ],
            )

            def get(harness, path):
                conn = http.client.HTTPConnection(
                    "127.0.0.1", harness.http_port, timeout=30
                )
                conn.request("GET", path)
                resp = conn.getresponse()
                return resp.status, resp.read().decode()

            _st, metrics = get(fused, "/metrics")
            check("fused /metrics exposes seldon_engine_fused_segments",
                  "seldon_engine_fused_segments" in metrics)
            check("clean path counts no fusion fallbacks",
                  "seldon_engine_fusion_fallbacks" not in metrics)

            st, fr_raw = get(fused, "/flightrecorder")
            check("/flightrecorder 200", st == 200)
            fr = json.loads(fr_raw)
            fusion_dump = (fr.get("units") or {}).get("(fusion)") or {}
            recs = [
                e for e in fusion_dump.get("entries", [])
                if e.get("type") == "fused_dispatch"
            ]
            check("(fusion) dump has fused_dispatch records",
                  len(recs) == len(reqs), f"{len(recs)} records")

            sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
            import flight_report

            rendered = flight_report.render(fr)
            check("flight_report renders fused segments",
                  "fused segment" in rendered, rendered[:200])

            chaos_outs = [_scrub(predict(chaos, r)) for r in reqs]
            check("chaos output identical per-unit", chaos_outs == plain_outs)
            _st, cmetrics = get(chaos, "/metrics")
            check(
                "chaos fallback counted",
                'seldon_engine_fusion_fallbacks' in cmetrics
                and 'reason="faults"' in cmetrics,
            )
        finally:
            plain.stop()
            fused.stop()
            chaos.stop()
            gen.close()

    print("PASS" if not failures else f"FAILED: {failures}")
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
