#!/usr/bin/env python3
"""CI smoke for the generation-path observability stack.

Boots a tiny generate server behind a real engine on sockets, runs a few
requests, then asserts the whole observability surface is live:

* ``/prometheus`` exposes the first-class SLO series
  (``seldon_engine_generate_ttft_seconds`` / ``..._tpot_seconds`` /
  ``..._queue_wait_seconds`` histograms) plus — with the device-time
  profiler and SLO burn engine on — the
  ``seldon_engine_device_time_seconds`` attribution counters and the
  ``seldon_engine_slo_burn_rate`` gauges;
* ``/flightrecorder`` returns well-formed JSON with per-poll records and
  an SLO summary (and ``tools/flight_report.py`` can render it,
  device-time ledger breakdown included);
* ``/traces`` shows a generate request as ONE stitched trace:
  queue-wait → prefill → decode spans under the engine's root span;
* a TWO-member deployment reconciled through the controller serves
  ``/fleet`` per member, the controller's scrape loop merges both into
  one deployment-scope metric plane, and an absurdly tight SLO
  objective forces a ``page`` burn verdict the autoscaler feed sees.

Run directly (``JAX_PLATFORMS=cpu python tools/observability_smoke.py``)
or from the CI observability step. Exits non-zero on any failed check.
"""

from __future__ import annotations

import json
import os
import sys
import tempfile


def main() -> int:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    import http.client

    from seldon_core_tpu.servers.generateserver import GenerateServer
    from seldon_core_tpu.testing import EngineHarness, write_model_dir
    from seldon_core_tpu.tracing import get_tracer, init_tracer

    init_tracer("obs-smoke", enabled=True)
    failures = []

    def check(name: str, ok: bool, detail: str = ""):
        print(f"{'ok  ' if ok else 'FAIL'} {name}" + (f": {detail}" if detail else ""))
        if not ok:
            failures.append(name)

    with tempfile.TemporaryDirectory(prefix="obs-smoke-") as root:
        model_dir = write_model_dir(root, "llm", {
            "vocab_size": 256, "d_model": 32, "n_layers": 2, "n_heads": 2,
            "n_kv_heads": 2, "d_ff": 64, "max_seq": 64,
        })
        component = GenerateServer(model_uri=model_dir, slots=2,
                                   steps_per_poll=4, attn_bucket=16,
                                   profiler=1, profiler_deep_every=3,
                                   profiler_hbm_gb_s=100.0,
                                   slo_objectives="ttft:0.001:0.99")
        component.load()
        harness = EngineHarness(component, name="obs-smoke").start()
        try:
            conn = http.client.HTTPConnection("127.0.0.1", harness.http_port)
            body = json.dumps({"jsonData": {
                "prompt_tokens": [[1, 2, 3, 4, 5]],
                "max_new_tokens": 6, "temperature": 0.0,
            }}).encode()
            for _ in range(3):
                conn.request("POST", "/api/v0.1/predictions", body,
                             {"Content-Type": "application/json"})
                resp = conn.getresponse()
                payload = resp.read()
                check("predict 200", resp.status == 200, payload[:120].decode("utf-8", "replace"))

            conn.request("GET", "/metrics")
            metrics = conn.getresponse().read().decode()
            for series in (
                "seldon_engine_generate_ttft_seconds",
                "seldon_engine_generate_tpot_seconds",
                "seldon_engine_generate_queue_wait_seconds",
            ):
                check(f"/metrics has {series}", f"{series}_bucket" in metrics)
            # device-time ledger exposition: attribution counters with a
            # kind label, the live-MBU gauge, and the burn-rate series a
            # 1µs TTFT objective forces into a paging verdict
            check("/metrics has seldon_engine_device_time_seconds{kind=}",
                  "seldon_engine_device_time_seconds" in metrics
                  and 'kind="prefill"' in metrics)
            for series in ("seldon_engine_device_dispatches",
                           "seldon_engine_mbu_pct",
                           "seldon_engine_slo_burn_rate",
                           "seldon_engine_slo_burn_verdicts"):
                check(f"/metrics has {series}", series in metrics)
            check("forced burn verdict pages",
                  'severity="page"' in metrics)

            conn.request("GET", "/flightrecorder")
            resp = conn.getresponse()
            check("/flightrecorder 200", resp.status == 200)
            fr = json.loads(resp.read())
            units = fr.get("units") or {}
            check("/flightrecorder has a unit dump", bool(units))
            dump = next(iter(units.values()), {})
            check("flight recorder recorded polls",
                  any(e.get("type") == "poll" for e in dump.get("entries", [])))
            check("flight recorder has SLO summary",
                  bool((dump.get("slo") or {}).get("samples")))

            sys.path.insert(0, os.path.dirname(__file__))
            from flight_report import render

            report = render(fr)
            check("flight_report renders", "flight report" in report
                  and "SLO over" in report)
            check("flight_report renders the device-time ledger",
                  "device-time ledger" in report)
            check("flight_report renders the burn verdict",
                  "SLO burn PAGE" in report)

            conn.request("GET", "/traces?operation=gen.")
            resp = conn.getresponse()
            check("/traces 200", resp.status == 200)
            traces = json.loads(resp.read())
            ops = {
                s["operationName"]
                for t in traces.get("data", [])
                for s in t.get("spans", [])
            }
            for op in ("gen.queue_wait", "gen.prefill", "gen.decode"):
                check(f"/traces has {op}", op in ops, str(sorted(ops)))
            # one request = one stitched trace: a gen.decode span shares its
            # trace id with the engine's root predictions span
            full = get_tracer().export_jaeger()
            stitched = False
            for t in full["data"]:
                names = {s["operationName"] for s in t["spans"]}
                if "predictions" in names and "gen.decode" in names:
                    stitched = True
            check("generate spans stitch under the engine root", stitched)
        finally:
            harness.stop()
            if component.batcher is not None:
                component.batcher.close()
            init_tracer(enabled=False)

    fleet_smoke(check)

    if failures:
        print(f"\nobservability smoke FAILED: {failures}", file=sys.stderr)
        return 1
    print("\nobservability smoke passed")
    return 0


def fleet_smoke(check) -> None:
    """Two-member deployment through the controller: every member serves
    ``/fleet``, the scrape loop merges both into the deployment-scope
    registry with member labels, and the 1µs TTFT objective forces a
    paging burn verdict into the autoscaler feed."""
    import asyncio
    import json
    import tempfile

    from seldon_core_tpu.controlplane.ingress import Gateway
    from seldon_core_tpu.controlplane.reconciler import DeploymentController
    from seldon_core_tpu.controlplane.resource import SeldonDeployment
    from seldon_core_tpu.controlplane.store import ResourceStore

    with tempfile.TemporaryDirectory(prefix="obs-smoke-fleet-") as root:
        import os

        model_dir = os.path.join(root, "llm")
        os.makedirs(model_dir)
        with open(os.path.join(model_dir, "jax_config.json"), "w") as f:
            json.dump({"family": "llm", "config": {
                "vocab_size": 256, "d_model": 32, "n_layers": 2,
                "n_heads": 2, "n_kv_heads": 2, "d_ff": 64, "max_seq": 64,
                "seed": 0,
            }}, f)
        dep = SeldonDeployment.from_dict({
            "metadata": {"name": "gen", "namespace": "default"},
            "spec": {"predictors": [{
                "name": "main", "traffic": 100, "replicas": 2,
                "graph": {
                    "name": "llm",
                    "implementation": "GENERATE_SERVER",
                    "modelUri": model_dir,
                    "parameters": [
                        {"name": "slots", "value": "2", "type": "INT"},
                        {"name": "max_seq", "value": "64", "type": "INT"},
                        {"name": "profiler", "value": "1", "type": "INT"},
                        {"name": "slo_objectives",
                         "value": "ttft:0.001:0.99", "type": "STRING"},
                    ],
                },
            }]},
        })

        async def run():
            store = ResourceStore()
            gw = Gateway(seed=0)
            ctl = DeploymentController(store, gateway=gw)
            try:
                store.apply(dep)
                status = await ctl.reconcile(dep)
                check("fleet: 2-member deployment reconciles",
                      status.state == "Available", status.description)
                check("fleet: two members placed",
                      len(ctl.components) == 2, str(list(ctl.components)))
                primary, _ = gw.select("default/gen")
                for i in range(3):
                    out = await gw._forward(
                        primary, "/api/v0.1/predictions",
                        {"jsonData": {"prompt_tokens": [[3, 17, 42]],
                                      "max_new_tokens": 5}},
                    )
                    check(f"fleet: predict {i} answered",
                          bool(out.get("jsonData", {}).get("tokens")))
                # every member answers /fleet (the scrape's input) with
                # mergeable primitives + unit summaries
                for name, (handle, _) in ctl.components.items():
                    snap = await handle.fleet()
                    check(f"fleet: member {name} serves /fleet",
                          snap is not None and "metrics" in snap
                          and "units" in snap)
                units = await ctl.fleet_scrape_once()
                check("fleet: scrape covered both members",
                      len(units) == 2, str(list(units)))
                text = ctl.fleet_metrics.expose()
                check("fleet: merged plane has device-time attribution",
                      "seldon_engine_device_time_seconds" in text)
                check("fleet: merged series carry member labels",
                      'member="' in text and 'deployment="' in text)
                series = "seldon_engine_generate_ttft_seconds"
                check("fleet: merged TTFT histogram buckets",
                      f"{series}_bucket" in text)
                verdicts = [
                    v for vs in ctl._burn_verdicts.values() for v in vs
                ]
                check("fleet: forced burn verdict pages",
                      any(v.get("severity") == "page" for v in verdicts),
                      str(verdicts[:2]))
                check("fleet: page verdict feeds the autoscaler signal",
                      any(
                          ctl._worst_burn(dep_key, pred) == "page"
                          for (dep_key, pred) in ctl._burn_verdicts
                      ))
            finally:
                await ctl.shutdown()

        asyncio.run(run())


if __name__ == "__main__":
    raise SystemExit(main())
