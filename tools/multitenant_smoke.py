#!/usr/bin/env python3
"""CI smoke for multi-tenant multi-model serving (generate.md §13).

Boots TWO tenants — distinct checkpoints, distinct SLO classes — on ONE
GenerateServer behind a real engine on sockets, plus a dedicated
single-tenant server per checkpoint as the identity reference, then
asserts:

* interleaved per-tenant traffic routed by the ``Seldon-Tenant`` header
  is byte-identical (greedy AND seeded sampling) to each tenant's
  dedicated server — every interleave step forces a demote→promote
  cycle of the other tenant, so the identity holds ACROSS weight paging;
* the pager actually paged (page-ins / switches counted) and a
  scale-to-zero tenant comes back without recompiling (jit cache sizes
  pinned across the cycle);
* an undeclared tenant is refused typed, not served the wrong weights;
* the ``seldon_engine_tenant_*`` + ``seldon_engine_weight_page*`` /
  ``seldon_engine_weight_pager_*`` series land in the Prometheus
  exposition, per-tenant series carrying the tenant label;
* ``flight_report`` renders the ``weight_page_in`` / ``weight_page_out``
  / ``tenant_switch`` records.

Run directly (``JAX_PLATFORMS=cpu python tools/multitenant_smoke.py``)
or from the CI multitenant_smoke step. Exits non-zero on any failure.
"""

from __future__ import annotations

import json
import os
import sys
import tempfile


def main() -> int:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    # runtime thread-role assertions (analysis/roles.py) fail the smoke
    # loudly on a scheduler-thread violation (must precede seldon imports)
    os.environ.setdefault("SELDON_DEBUG_THREADS", "1")
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    import http.client

    from seldon_core_tpu.graph.engine_metrics import REGISTRY
    from seldon_core_tpu.servers.generateserver import GenerateServer
    from seldon_core_tpu.testing import EngineHarness, write_model_dir

    failures = []

    def check(name: str, ok: bool, detail: str = ""):
        print(f"{'ok  ' if ok else 'FAIL'} {name}" + (f": {detail}" if detail else ""))
        if not ok:
            failures.append(name)

    with tempfile.TemporaryDirectory(prefix="multitenant-smoke-") as root:
        cfg = {"vocab_size": 256, "d_model": 32, "n_layers": 2, "n_heads": 4,
               "n_kv_heads": 2, "d_ff": 64, "max_seq": 64}
        # distinct weights per tenant: jaxserver random-inits from the
        # config seed, so same architecture + different seed = a second
        # checkpoint that MUST produce different tokens
        dir_a = write_model_dir(os.path.join(root, "a"), "llm", cfg)
        dir_b = write_model_dir(
            os.path.join(root, "b"), "llm", {**cfg, "seed": 7}
        )
        common = dict(slots=2, steps_per_poll=2, warmup_prompt_lens=[4],
                      warmup_max_new_tokens=8)

        ded_a = GenerateServer(model_uri=dir_a, **common)
        ded_a.load()
        ded_b = GenerateServer(model_uri=dir_b, **common)
        ded_b.load()
        multi = GenerateServer(
            model_uri=dir_a,
            tenants=f"acme=strict,globex=best_effort@{dir_b}",
            weight_pager_host_bytes=64 << 20,
            tenant_min_resident_ms=0,
            **common,
        )
        multi.load()

        h_a = EngineHarness(ded_a, name="dedicated-acme").start()
        h_b = EngineHarness(ded_b, name="dedicated-globex").start()
        h_m = EngineHarness(multi, name="multitenant").start()

        def gen(port: int, prompt, tenant=None, temperature=0.0,
                seed=0, want_status=200):
            headers = {"Content-Type": "application/json"}
            if tenant is not None:
                headers["Seldon-Tenant"] = tenant
            conn = http.client.HTTPConnection("127.0.0.1", port)
            conn.request("POST", "/api/v0.1/predictions", json.dumps({
                "jsonData": {"prompt_tokens": [prompt], "max_new_tokens": 8,
                             "temperature": temperature, "seed": seed},
            }).encode(), headers)
            resp = conn.getresponse()
            payload = resp.read()
            conn.close()
            if resp.status != want_status:
                raise RuntimeError(f"HTTP {resp.status}: {payload[:160]!r}")
            if want_status != 200:
                return None
            return json.loads(payload)["jsonData"]["tokens"][0]

        try:
            prompts = [[5, 6, 7, 8], [9, 10, 11], [1, 2, 3, 4, 5, 6]]
            refs = {
                "acme": [gen(h_a.http_port, p) for p in prompts],
                "globex": [gen(h_b.http_port, p) for p in prompts],
            }
            # the two checkpoints really differ — otherwise identity
            # below would pass vacuously
            check("tenants serve distinct weights",
                  refs["acme"] != refs["globex"])

            # -- interleaved traffic: identity ACROSS paging --------------
            # alternate tenants per prompt so every request straddles a
            # demote→promote cycle of the other tenant
            for i, p in enumerate(prompts):
                for t in ("acme", "globex"):
                    got = gen(h_m.http_port, p, tenant=t)
                    check(f"greedy identical ({t}, prompt {i})",
                          got == refs[t][i],
                          "" if got == refs[t][i] else f"{got} != {refs[t][i]}")
            for i, p in enumerate(prompts):
                for t, port in (("acme", h_a.http_port),
                                ("globex", h_b.http_port)):
                    ref = gen(port, p, temperature=0.8, seed=17 + i)
                    got = gen(h_m.http_port, p, tenant=t,
                              temperature=0.8, seed=17 + i)
                    check(f"seeded identical ({t}, prompt {i})", got == ref,
                          "" if got == ref else f"{got} != {ref}")

            pstats = multi.tenant_pager.stats
            sstats = multi.tenant_scheduler.stats
            check("the interleave actually paged",
                  pstats["page_ins"] >= 3 and sstats["switches"] >= 2,
                  f"page_ins={pstats['page_ins']} switches={sstats['switches']}")

            # -- scale-to-zero: page back in without recompiling ----------
            b = multi.batcher
            sizes = {
                n: f._cache_size()
                for n, f in (("prefill", b._prefill_fn),
                             ("burst", b._burst_fn)) if f is not None
            }
            gen(h_m.http_port, prompts[0], tenant="globex")  # acme out
            gen(h_m.http_port, prompts[0], tenant="acme")    # ...and back
            recompiled = [
                n for n, f in (("prefill", b._prefill_fn),
                               ("burst", b._burst_fn))
                if f is not None and n in sizes and f._cache_size() != sizes[n]
            ]
            check("demote→promote cycle recompiled nothing",
                  not recompiled, f"recompiled={recompiled}")

            # -- unknown tenant refused typed -----------------------------
            try:
                gen(h_m.http_port, prompts[0], tenant="nobody")
                check("undeclared tenant refused", False, "served!")
            except RuntimeError as e:
                check("undeclared tenant refused", "200" not in str(e)[:12],
                      str(e)[:80])

            # -- exposition: tenant + pager series ------------------------
            expo = REGISTRY.expose()
            for series in ("seldon_engine_tenant_requests",
                           "seldon_engine_tenant_switches",
                           "seldon_engine_weight_page_ins",
                           "seldon_engine_weight_page_outs",
                           "seldon_engine_weight_pager_host_bytes",
                           "seldon_engine_weight_pager_resident_bytes",
                           "seldon_engine_tenants_registered",
                           "seldon_engine_tenant_ttft_seconds",
                           "seldon_engine_tenant_queue_wait_seconds"):
                check(f"exposition has {series}", series in expo)
            check("per-tenant series carry the tenant label",
                  'tenant="acme"' in expo and 'tenant="globex"' in expo)

            # -- flight report renders the paging story -------------------
            import importlib.util

            fr = os.path.join(os.path.dirname(__file__), "flight_report.py")
            spec = importlib.util.spec_from_file_location("flight_report", fr)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            text = mod.render(multi.flight_dump())
            check("flight report renders tenant switches",
                  "tenant switches:" in text)
            check("flight report renders the pager",
                  "weight pager:" in text and "weight pager staging" in text)
        finally:
            h_a.stop()
            h_b.stop()
            h_m.stop()
            ded_a.close()
            ded_b.close()
            multi.close()

    if failures:
        print(f"\nmultitenant smoke FAILED: {failures}", file=sys.stderr)
        return 1
    print("\nmultitenant smoke passed")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
