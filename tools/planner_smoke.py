#!/usr/bin/env python3
"""CI smoke for the autonomic serving planner (operate.md §"Autonomic
planning"): profiler sweep -> SPF1 artifact -> controller tick ->
retune through the safe path, on a real engine.

Flow:

* sweeps a REAL tiny engine through a 2-point config grid under one
  seeded TrafficSim trace (``run_sweep``), asserting the SPF1 artifact
  round-trips, refuses truncation typed, and yields a monotone cost
  model;
* boots a GENERATE_SERVER deployment through the store/reconciler with
  ``seldon.io/planner`` + ``seldon.io/planner-profile`` annotations,
  drives a trafficsim burst through its scheduler, scrapes the fleet
  plane, and ticks the planner: a warn-severity burn verdict must
  actuate a retune THROUGH the safe path (``retune()`` at a poll
  boundary) — verified by re-scraping ``/fleet``'s planning block and
  by greedy byte-identity across the retune;
* asserts the ``seldon_engine_planner_retunes`` exposition and the
  controller's planner stats;
* renders the ``planner_retune`` flight records through
  ``flight_report`` — including the THRASHING DIAGNOSIS once a knob
  is flipped straight back;
* regression-checks the planner/autoscaler precedence: a page burn
  verdict VETOES a same-tick scale-down at the actuation site.

Run directly (``JAX_PLATFORMS=cpu python tools/planner_smoke.py``) or
from the CI planner_smoke step. Exits non-zero on any failure.
"""

from __future__ import annotations

import asyncio
import json
import os
import sys
import tempfile


def main() -> int:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ.setdefault("SELDON_DEBUG_THREADS", "1")
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

    from seldon_core_tpu.models.llm import DecoderLM
    from seldon_core_tpu.planning import (
        CostModel,
        ServingPlanner,
        TrafficSim,
        build_profile,
        read_profile,
        replay,
        run_sweep,
        sweep_grid,
        write_profile,
    )
    from seldon_core_tpu.serving.continuous import ContinuousBatcher
    from seldon_core_tpu.serving.disagg import TruncatedStream

    failures = []

    def check(name: str, ok: bool, detail: str = ""):
        print(f"{'ok  ' if ok else 'FAIL'} {name}"
              + (f": {detail}" if detail else ""))
        if not ok:
            failures.append(name)

    cfg = {"vocab_size": 256, "d_model": 32, "n_layers": 2, "n_heads": 4,
           "n_kv_heads": 2, "d_ff": 64, "max_seq": 64}

    # -- offline: sweep a real engine into an SPF1 artifact ------------------
    model = DecoderLM(**cfg)
    params = model.init_params(0)
    sim = TrafficSim(
        seed=42, duration_s=8, base_rps=4, tenants=3, prompt_families=4,
        prefix_len=8, suffix_len=(2, 10), vocab=256,
        max_new_tokens=(4, 12), deadline_s=(2.0, 10.0), deadline_frac=0.3,
    )

    def factory(config):
        return ContinuousBatcher(
            model, params,
            slots=config["slots"],
            fused_steps_per_dispatch=config["fused_steps_per_dispatch"],
            max_seq=64, prefill_buckets=(8, 16, 32), steps_per_poll=2,
        )

    profile = run_sweep(
        factory, sweep_grid(slots=(2,), fused_steps=(0, 8)), sim,
        model_family="llm-smoke", max_events=8,
    )
    check("sweep priced every grid point", len(profile["grid"]) == 2)
    check("sweep measured real tokens",
          all(e["tokens_per_s"] > 0 for e in profile["grid"]),
          json.dumps([e["tokens_per_s"] for e in profile["grid"]]))
    check("sweep recorded the compile census",
          all(e["compile_census"]["variants"] >= 1
              and e["compile_census"]["compile_s"] > 0
              for e in profile["grid"]))

    with tempfile.TemporaryDirectory(prefix="planner-smoke-") as root:
        swept = os.path.join(root, "swept.spf1")
        write_profile(swept, profile)
        check("SPF1 artifact round-trips", read_profile(swept) == profile)
        try:
            with open(swept, "rb") as f:
                from seldon_core_tpu.planning import decode_profile

                decode_profile(f.read()[:-4])
            check("truncated SPF1 refuses typed", False, "decoded!")
        except TruncatedStream:
            check("truncated SPF1 refuses typed", True)
        cm = CostModel(profile)
        preds = [
            cm.predict({"slots": 2, "fused_steps_per_dispatch": k})
            ["tokens_per_s"]
            for k in (0, 2, 8, 32)
        ]
        check("cost model monotone in fused K", preds == sorted(preds),
              json.dumps([round(p, 1) for p in preds]))

        # the closed-loop leg plans over a DETERMINISTIC profile (the
        # swept numbers above are real but noisy on shared CI chips):
        # fused=8 breaches the warn objective, fused=4 meets it, so the
        # decision table must pick the 8 -> 4 retune
        plan_profile = os.path.join(root, "plan.spf1")
        write_profile(plan_profile, build_profile("llm-smoke", [
            {"config": {"slots": 2, "prefill_chunk": 0,
                        "fused_steps_per_dispatch": 8, "kv_tier_bytes": 0},
             "tokens_per_s": 200.0, "ttft_p50_ms": 400.0,
             "ttft_p99_ms": 900.0, "tpot_p50_ms": 30.0,
             "tpot_p99_ms": 60.0, "hbm_bytes": 10**9},
            {"config": {"slots": 2, "prefill_chunk": 0,
                        "fused_steps_per_dispatch": 4, "kv_tier_bytes": 0},
             "tokens_per_s": 300.0, "ttft_p50_ms": 120.0,
             "ttft_p99_ms": 250.0, "tpot_p50_ms": 8.0,
             "tpot_p99_ms": 15.0, "hbm_bytes": 10**9},
        ]))

        model_dir = os.path.join(root, "llm")
        os.makedirs(model_dir)
        with open(os.path.join(model_dir, "jax_config.json"), "w") as f:
            json.dump({"family": "llm", "config": {**cfg, "seed": 0}}, f)

        asyncio.run(closed_loop(check, model_dir, plan_profile, sim))

    if failures:
        print(f"\nplanner smoke FAILED: {failures}", file=sys.stderr)
        return 1
    print("\nplanner smoke passed")
    return 0


async def closed_loop(check, model_dir, profile_path, sim) -> None:
    import importlib.util

    from seldon_core_tpu.controlplane.reconciler import DeploymentController
    from seldon_core_tpu.controlplane.resource import SeldonDeployment
    from seldon_core_tpu.controlplane.store import ResourceStore
    from seldon_core_tpu.graph.engine_metrics import REGISTRY
    from seldon_core_tpu.planning import Decision, replay

    store = ResourceStore()
    ctl = DeploymentController(store)
    dep, _ = store.apply(SeldonDeployment.from_dict({
        "metadata": {"name": "gen", "namespace": "default"},
        "spec": {"predictors": [{
            "name": "main",
            "replicas": 1,
            "annotations": {
                "seldon.io/planner": "true",
                "seldon.io/planner-profile": profile_path,
            },
            "graph": {
                "name": "llm", "implementation": "GENERATE_SERVER",
                "modelUri": model_dir,
                "parameters": [
                    {"name": "slots", "value": "2", "type": "INT"},
                    {"name": "max_seq", "value": "64", "type": "INT"},
                    {"name": "steps_per_poll", "value": "2", "type": "INT"},
                    {"name": "fused_steps_per_dispatch", "value": "8",
                     "type": "INT"},
                ],
            },
        }]},
    }))
    status = await ctl.reconcile(dep.clone())
    check("planner-annotated deployment reconciles",
          status.state == "Available", status.description or "")

    try:
        # the live GenerateServer unit behind the in-process handle
        srv = None
        for handle, _ in ctl.components.values():
            for _name, target in handle.app.units_with("serving_config"):
                srv = target
        check("engine unit found", srv is not None)

        # greedy references BEFORE any retune — identity must hold across
        prompts = [[3, 17, 42, 99], [9, 8, 7], [1, 2, 3, 4, 5]]
        refs = [srv.batcher.generate(p, max_new_tokens=8) for p in prompts]

        # a trafficsim burst through the scheduler (SLO samples + load)
        trace = sim.trace(max_events=10)
        handles = replay(
            trace,
            lambda ev: srv.batcher.submit(
                ev.prompt, max_new_tokens=ev.max_new_tokens,
                tenant=ev.tenant, deadline_s=ev.deadline_s,
            ),
        )
        served = sum(1 for h in handles if h.result(timeout=120) is not None)
        check("trafficsim burst served", served == len(trace),
              f"{served}/{len(trace)}")

        # fleet scrape: the planner's ONLY telemetry source
        await ctl.fleet_scrape_once()
        plan_blocks = [
            unit.get("planning")
            for units in ctl._fleet_units.values()
            for unit in units.values()
            if unit.get("planning")
        ]
        check("/fleet carries the planning block",
              bool(plan_blocks)
              and plan_blocks[0]["config"]["fused_steps_per_dispatch"] == 8
              and 4 in plan_blocks[0]["census"]["fused_ks"],
              json.dumps(plan_blocks[:1]))

        # warn-severity burn (what the scrape would accumulate during a
        # storm) -> the decision table must retune 8 -> 4 via the profile
        ctl._burn_verdicts[(dep.key, "main")] = [
            {"slo": "ttft_p99", "severity": "warn", "threshold_s": 0.5},
        ]
        events = await ctl.planner_tick_once()
        ev = events.get(f"{dep.key}/main") or {}
        check("planner tick decided a retune",
              ev.get("action") == "retune"
              and ev.get("knobs") == {"fused_steps_per_dispatch": 4}
              and ev.get("retuned", 0) >= 1,
              json.dumps(ev))

        # the knob actually moved, observed through the SAME fleet plane
        await ctl.fleet_scrape_once()
        cfgs = [
            unit["planning"]["config"]["fused_steps_per_dispatch"]
            for units in ctl._fleet_units.values()
            for unit in units.values()
            if unit.get("planning")
        ]
        check("retune landed at the poll boundary", cfgs == [4],
              json.dumps(cfgs))
        check("controller counted the retune",
              ctl.fleet_summary()["planner"]["stats"]["retunes"] == 1)

        # byte identity across the live retune — greedy streams unchanged
        got = [srv.batcher.generate(p, max_new_tokens=8) for p in prompts]
        check("greedy byte-identical across retune", got == refs)

        # exposition: the planner series rides the recovery-metric path
        REGISTRY.record_custom(srv.metrics())
        expo = REGISTRY.expose()
        check("exposition has seldon_engine_planner_retunes",
              "seldon_engine_planner_retunes" in expo)

        # flip the knob straight back and forth: flight_report must
        # render the planner_retune records AND diagnose the thrash
        for handle, _ in ctl.components.values():
            await handle.retune({"fused_steps_per_dispatch": 8})
            await handle.retune({"fused_steps_per_dispatch": 4})
        fr = os.path.join(os.path.dirname(__file__), "flight_report.py")
        spec = importlib.util.spec_from_file_location("flight_report", fr)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        text = mod.render(srv.flight_dump())
        check("flight report renders planner retunes",
              "planner retunes: 3 applied at poll boundaries" in text,
              text.splitlines()[0] if text else "")
        check("flight report diagnoses retune thrash",
              "THRASHING" in text and "fused_steps_per_dispatch" in text)

        # precedence regression: page burn vetoes a same-tick scale-down
        ctl._burn_verdicts[(dep.key, "main")] = [
            {"slo": "ttft_p99", "severity": "page"},
        ]
        out = await ctl._planner_actuate(
            dep, dep.predictors[0], Decision("scale_down", "idle", rank=6)
        )
        check("page burn vetoes planner scale-down",
              out == {"vetoed": True}
              and ctl.planner_stats["vetoes"] == 1
              and store.get("gen").predictors[0].replicas == 1,
              json.dumps(out))
    finally:
        await ctl.shutdown()


if __name__ == "__main__":
    raise SystemExit(main())
