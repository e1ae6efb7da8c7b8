"""Autonomic serving planner (planning/): profile artifact, cost model,
traffic simulator, decision table, retune actuation, fusion cost gate.

The load-bearing contracts: (1) a corrupt SPF1 profile refuses TYPED
(truncation / bit-flip / bad magic / bad grid) before the planner can
steer on it; (2) the cost model's fits are structurally monotone —
predicted tokens/s never decreases in fused K, predicted HBM never
decreases in slots — because both coefficients are clamped; (3) a
planner retune applies at a poll boundary and greedy AND seeded outputs
stay byte-identical across it; (4) the planner/autoscaler same-tick
precedence is deterministic: a page-severity burn verdict VETOES any
scale-down at the actuation site, and the two controllers share ONE
scale-down hysteresis; (5) the fusion cost gate flags exactly the
segments whose compile cost exceeds their amortized dispatch savings —
and nothing else.
"""

import asyncio
import json

import pytest

from seldon_core_tpu.planning import (
    CONFIG_KEYS,
    CostModel,
    Decision,
    ProfileError,
    ServingPlanner,
    TrafficSim,
    build_profile,
    decode_profile,
    encode_profile,
    read_profile,
    replay,
    sweep_grid,
    write_profile,
)
from seldon_core_tpu.serving.disagg import ChecksumError, TruncatedStream


def run(coro):
    return asyncio.run(coro)


def entry(slots=4, fused=0, tps=100.0, ttft=800.0, tpot=50.0,
          hbm=1_000_000_000, chunk=0, kv=0, **extra):
    return {
        "config": {
            "slots": slots, "prefill_chunk": chunk,
            "fused_steps_per_dispatch": fused, "kv_tier_bytes": kv,
        },
        "tokens_per_s": tps,
        "ttft_p50_ms": ttft / 2, "ttft_p99_ms": ttft,
        "tpot_p50_ms": tpot / 2, "tpot_p99_ms": tpot,
        "hbm_bytes": hbm,
        **extra,
    }


def profile(*entries, family="tiny"):
    return build_profile(family, list(entries))


GRID3 = (
    entry(slots=4, fused=0, tps=100, ttft=800, tpot=50, hbm=10**9),
    entry(slots=4, fused=8, tps=400, ttft=300, tpot=20, hbm=10**9),
    entry(slots=8, fused=8, tps=600, ttft=250, tpot=15, hbm=2 * 10**9),
)


# -- SPF1 codec: round-trip + typed corruption refusal ------------------------


def test_profile_round_trip(tmp_path):
    prof = profile(*GRID3)
    assert decode_profile(encode_profile(prof)) == prof
    p = tmp_path / "tiny.spf1"
    write_profile(str(p), prof)
    assert read_profile(str(p)) == prof


def test_profile_truncation_refuses_typed():
    data = encode_profile(profile(*GRID3))
    with pytest.raises(TruncatedStream):
        decode_profile(data[:8])          # shorter than the frame header
    with pytest.raises(TruncatedStream):
        decode_profile(data[:-5])         # payload cut mid-JSON
    with pytest.raises(TruncatedStream):
        decode_profile(b"")


def test_profile_bit_flip_refuses_typed():
    data = bytearray(encode_profile(profile(*GRID3)))
    data[20] ^= 0x40                      # one flipped bit in the payload
    with pytest.raises(ChecksumError):
        decode_profile(bytes(data))


def test_profile_bad_magic_and_version_refuse_typed():
    data = encode_profile(profile(*GRID3))
    with pytest.raises(ProfileError, match="magic"):
        decode_profile(b"XXXX" + data[4:])
    # a future version must refuse on decode, not half-parse — frame one
    # by hand since encode_profile validates too
    import struct
    import zlib

    bad = dict(profile(*GRID3))
    bad["v"] = 99
    payload = json.dumps(bad).encode()
    frame = b"SPF1" + struct.pack(
        "<II", len(payload), zlib.crc32(payload)
    ) + payload
    with pytest.raises(ProfileError, match="version"):
        decode_profile(frame)


def test_profile_with_the_retired_depth_group_axes_refuses_typed():
    """A version-1 artifact keyed its grid on ``depth_groups`` and
    ``depth_group_split_bytes`` too. Those knobs are gone with the
    mechanism, so such a file is refused by its version, typed, on
    decode — never half-read into a grid whose configs collide."""
    import struct
    import zlib

    old = dict(profile(*GRID3), v=1)
    old["grid"] = [
        dict(e, config=dict(e["config"], depth_groups=2 * i,
                            depth_group_split_bytes=0))
        for i, e in enumerate(old["grid"])
    ]
    payload = json.dumps(old).encode()
    frame = b"SPF1" + struct.pack(
        "<II", len(payload), zlib.crc32(payload)
    ) + payload
    with pytest.raises(ProfileError, match="version 1"):
        decode_profile(frame)
    assert profile(*GRID3)["v"] == 2
    assert "depth_groups" not in profile(*GRID3)["grid"][0]["config"]


def test_profile_malformed_grid_refuses_on_both_sides():
    with pytest.raises(ProfileError, match="empty"):
        build_profile("tiny", [])
    # duplicate config = two prices for one identity: ambiguous, refused
    with pytest.raises(ProfileError, match="duplicates"):
        build_profile("tiny", [entry(slots=4), entry(slots=4)])
    bad = entry(slots=4)
    bad["tokens_per_s"] = -1.0
    with pytest.raises(ProfileError, match="tokens_per_s"):
        build_profile("tiny", [bad])
    missing = entry(slots=4)
    del missing["config"]["kv_tier_bytes"]
    with pytest.raises(ProfileError, match="kv_tier_bytes"):
        build_profile("tiny", [missing])


def test_sweep_grid_covers_axes_uniquely():
    grid = sweep_grid(slots=(4, 8), fused_steps=(0, 4, 8))
    assert len(grid) == 6
    keys = {tuple(c[k] for k in CONFIG_KEYS) for c in grid}
    assert len(keys) == 6                 # no duplicate configs
    assert all(set(c) == set(CONFIG_KEYS) for c in grid)


# -- cost model: structural monotonicity + ranking ---------------------------


def test_cost_model_tokens_per_s_monotone_in_fused_k():
    """Even an adversarial grid (a measured point where a HIGHER K came
    out slower — live noise) cannot break the fit's monotonicity: the
    dispatch-floor coefficient is clamped >= 0."""
    noisy = profile(
        entry(slots=4, fused=0, tps=100),
        entry(slots=4, fused=4, tps=300),
        entry(slots=4, fused=8, tps=290),   # adversarial: slower than K=4
    )
    cm = CostModel(noisy)
    preds = [
        cm.predict({"slots": 4, "fused_steps_per_dispatch": k})["tokens_per_s"]
        for k in (0, 1, 2, 4, 8, 16, 32)
    ]
    assert preds == sorted(preds)
    assert all(p > 0 for p in preds)


def test_cost_model_hbm_monotone_in_slots():
    noisy = profile(
        entry(slots=2, fused=0, hbm=3 * 10**9),  # adversarial: big at 2
        entry(slots=4, fused=4, hbm=10**9),
        entry(slots=8, fused=8, hbm=2 * 10**9),
    )
    cm = CostModel(noisy)
    preds = [cm.predict({"slots": s})["hbm_bytes"] for s in (1, 2, 4, 8, 16)]
    assert preds == sorted(preds)
    assert all(p >= 0 for p in preds)


def test_cost_model_price_is_exact_match_only():
    cm = CostModel(profile(*GRID3))
    assert cm.price({"slots": 4, "fused_steps_per_dispatch": 8}) is not None
    assert cm.price({"slots": 4, "fused_steps_per_dispatch": 2}) is None


def test_cost_model_best_ranks_and_pins():
    cm = CostModel(profile(*GRID3))
    # unpinned: the 8-slot config wins on throughput
    out = cm.best(ttft_p99_ms=500, tpot_p99_ms=30)
    assert out["meets"] and out["config"]["slots"] == 8
    # require pins the census reality: only this member's slot count
    out = cm.best(ttft_p99_ms=500, tpot_p99_ms=30, require={"slots": 4})
    assert out["meets"] and out["config"] == GRID3[1]["config"]
    # nothing meets -> smallest worst breach, flagged (a scale signal)
    out = cm.best(ttft_p99_ms=100, tpot_p99_ms=5)
    assert out["meets"] is False and out["worst_breach"] > 1.0
    # hard constraints with no candidate at all refuse typed
    with pytest.raises(ProfileError):
        cm.best(ttft_p99_ms=500, require={"slots": 99})


def test_cost_model_best_hbm_budget_is_hard():
    cm = CostModel(profile(*GRID3))
    out = cm.best(ttft_p99_ms=500, tpot_p99_ms=30,
                  hbm_budget_bytes=int(1.5 * 10**9))
    assert out["config"]["slots"] == 4     # the 2 GB config is excluded


def test_fusion_gate_priced_from_compile_census():
    from seldon_core_tpu.graph.fusion import segment_worth_compiling

    prof = profile(
        entry(slots=4, fused=0, tps=100,
              compile_census={"variants": 2, "compile_s": 4.0}),
        entry(slots=4, fused=8, tps=400,
              compile_census={"variants": 4, "compile_s": 8.0}),
    )
    gate = CostModel(prof).fusion_gate(expected_dispatches=1000)
    assert gate["expected_dispatches"] == 1000
    assert gate["compile_cost_s"] == pytest.approx(2.0)  # mean s/variant
    assert gate["dispatch_floor_us"] > 0   # K=8 measured faster -> floor
    # the same gate drives segment_worth_compiling both ways: enough
    # volume amortizes the compile, a trickle does not
    rich = dict(gate, expected_dispatches=10**9)
    assert segment_worth_compiling(2, rich)
    poor = dict(gate, expected_dispatches=1)
    assert not segment_worth_compiling(2, poor)


# -- traffic simulator: seeded determinism ------------------------------------


def test_trafficsim_same_seed_same_trace():
    a = TrafficSim(seed=7, duration_s=30).trace()
    b = TrafficSim(seed=7, duration_s=30).trace()
    assert a == b and len(a) > 50


def test_trafficsim_different_seed_different_trace():
    a = TrafficSim(seed=7, duration_s=30).trace()
    b = TrafficSim(seed=8, duration_s=30).trace()
    assert a != b


def test_trafficsim_prefixes_survive_arrival_knob_changes():
    """Family prefixes derive from the seed alone — retuning the
    ARRIVAL process (rate, burstiness) must not reshuffle every
    family's shared prefix, or prefix-cache comparisons across load
    levels would be meaningless."""
    a = TrafficSim(seed=5)
    b = TrafficSim(seed=5, base_rps=40, burst_mult=8, gamma_shape=1.0)
    assert a._prefixes == b._prefixes
    ev = TrafficSim(seed=5, duration_s=20).trace()[0]
    assert ev.prompt[:a.prefix_len] == a._prefixes[ev.family]


def test_trafficsim_shape_and_summary():
    sim = TrafficSim(seed=3, duration_s=60, tenants=6, deadline_frac=0.5)
    trace = sim.trace()
    s = sim.summary(trace)
    assert s["events"] == len(trace)
    assert s["tenants"] <= 6
    # Zipf: the hottest tenant carries more than a uniform share
    assert s["hottest_tenant_frac"] > 1.0 / 6
    assert 0.2 < s["deadline_frac"] < 0.8
    assert all(ev.t <= 60 for ev in trace)
    assert all(ev.t >= prev.t for prev, ev in zip(trace, trace[1:]))
    lo, hi = sim.deadline_bounds
    assert all(
        lo <= ev.deadline_s <= hi
        for ev in trace if ev.deadline_s is not None
    )


def test_trafficsim_replay_orders_and_paces():
    trace = TrafficSim(seed=9, duration_s=10).trace(max_events=20)
    seen = []
    handles = replay(trace, lambda ev: seen.append(ev) or len(seen))
    assert handles == list(range(1, len(trace) + 1))
    assert seen == trace
    # paced replay sleeps toward each arrival offset on the fake clock
    clock = {"t": 0.0}
    slept = []

    def sleep(d):
        slept.append(d)
        clock["t"] += d

    replay(trace, lambda ev: ev, time_scale=1.0,
           clock=lambda: clock["t"], sleep=sleep)
    assert slept and all(d >= 0 for d in slept)
    assert clock["t"] == pytest.approx(trace[-1].t)


# -- planner decision table ---------------------------------------------------


CENSUS = {"fused_ks": (2, 4, 8), "prefill_chunk": 0, "pipeline_depth": 1}
CONFIG0 = dict(GRID3[0]["config"])        # slots=4, fused=0


def warn(slo="ttft_p99", thr=0.5):
    return {"slo": slo, "severity": "warn", "threshold_s": thr}


def test_planner_rank1_page_scales_up_and_resets_idle_credit():
    p = ServingPlanner(scale_down_ticks=2)
    # bank an idle tick first...
    d = p.tick(gauges={"device_busy_frac": 0.01})
    assert d.action == "hold" and d.rank == 6
    # ...then a page tick: scale up AND the idle streak is gone
    d = p.tick(verdicts=[{"slo": "x", "severity": "page"}])
    assert d.action == "scale_up" and d.rank == 1
    d = p.tick(gauges={"device_busy_frac": 0.01})
    assert d.action == "hold"             # streak restarted from zero


def test_planner_rank2_sustained_pressure_scales_up():
    p = ServingPlanner(hot_ticks=2)
    totals = {"sheds": 5.0, "preemptions": 0.0}
    d = p.tick(verdicts=[warn()], counter_totals=totals)
    assert d.action == "hold" and d.rank == 2
    totals = {"sheds": 9.0, "preemptions": 1.0}
    d = p.tick(verdicts=[warn()], counter_totals=totals)
    assert d.action == "scale_up" and d.rank == 2


def test_planner_rank3_warn_retunes_toward_measured_config():
    p = ServingPlanner(cost_model=CostModel(profile(*GRID3)))
    d = p.tick(verdicts=[warn("ttft_p99", 0.5), warn("tpot_p99", 0.03)],
               current_config=CONFIG0, census=CENSUS)
    assert d.action == "retune" and d.rank == 3
    # slots stay pinned (boot-time); only retunable axes appear
    assert d.knobs == {"fused_steps_per_dispatch": 8}


def test_planner_never_churns_unswept_axes():
    """An axis every grid entry shares (never swept) carries no
    measured evidence — the planner must not 'retune' the member's
    live value (here its boot chunk size) to the grid's constant."""
    p = ServingPlanner(cost_model=CostModel(profile(*GRID3)))
    live = dict(CONFIG0, prefill_chunk=32)
    d = p.tick(verdicts=[warn("ttft_p99", 0.5), warn("tpot_p99", 0.03)],
               current_config=live, census=CENSUS)
    assert d.action == "retune"
    assert d.knobs == {"fused_steps_per_dispatch": 8}


def test_planner_rank4_warn_without_meeting_config_scales_up():
    p = ServingPlanner(cost_model=CostModel(profile(*GRID3)))
    d = p.tick(verdicts=[warn("ttft_p99", 0.01)],   # nothing meets 10ms
               current_config=CONFIG0, census=CENSUS)
    assert d.action == "scale_up" and d.rank == 4
    # no cost model at all degrades the same way: capacity, not tuning
    d = ServingPlanner().tick(verdicts=[warn()], current_config=CONFIG0)
    assert d.action == "scale_up" and d.rank == 4


def test_planner_rank5_quiet_sheds_raise_watermark_bounded():
    p = ServingPlanner()
    d = p.tick(counter_totals={"sheds": 4.0},
               gauges={"pressure_high": 0.80})
    assert d.action == "retune" and d.rank == 5
    assert d.knobs == {"pressure_high": pytest.approx(0.85)}
    # at the ceiling there is no headroom: hold, never overshoot
    p2 = ServingPlanner()
    d = p2.tick(counter_totals={"sheds": 4.0},
                gauges={"pressure_high": 0.94})
    assert d.action == "hold" and d.rank == 5


def test_planner_rank6_idle_scale_down_needs_full_streak():
    p = ServingPlanner(scale_down_ticks=3)
    for i in range(2):
        assert p.tick(gauges={"device_busy_frac": 0.02}).action == "hold"
    d = p.tick(gauges={"device_busy_frac": 0.02})
    assert d.action == "scale_down" and d.rank == 6
    # a busy tick in the middle resets the bank
    p = ServingPlanner(scale_down_ticks=2)
    p.tick(gauges={"device_busy_frac": 0.02})
    p.tick(gauges={"device_busy_frac": 0.9})
    assert p.tick(gauges={"device_busy_frac": 0.02}).action == "hold"


def test_planner_retune_cooldown_is_refractory():
    p = ServingPlanner(cost_model=CostModel(profile(*GRID3)),
                       retune_cooldown_ticks=2)
    assert p.tick(verdicts=[warn()], current_config=CONFIG0,
                  census=CENSUS).action == "retune"
    d = p.tick(verdicts=[warn()], current_config=CONFIG0, census=CENSUS)
    assert d.action == "hold" and "cooldown" in d.reason
    # cooldown_ticks=2: the next retune is possible 2 ticks after the
    # last one, never sooner
    d = p.tick(verdicts=[warn()], current_config=CONFIG0, census=CENSUS)
    assert d.action == "retune"


def test_planner_counter_reset_never_goes_negative():
    p = ServingPlanner(hot_ticks=1)
    p.tick(counter_totals={"sheds": 50.0})
    # member restart: cumulative counter resets below the last total
    d = p.tick(verdicts=[warn()], counter_totals={"sheds": 0.0})
    assert d.action != "scale_up" or d.rank != 2


# -- planner/autoscaler precedence (actuation site) --------------------------


def make_controller():
    from seldon_core_tpu.controlplane import (
        DeploymentController, ResourceStore, SeldonDeployment,
    )
    from seldon_core_tpu.controlplane.runtime import InProcessRuntime

    store = ResourceStore()
    ctl = DeploymentController(
        store, runtime=InProcessRuntime(open_ports=False)
    )
    dep, _ = store.apply(SeldonDeployment.from_dict({
        "name": "gdep",
        "predictors": [{
            "name": "p0", "replicas": 2,
            "annotations": {"seldon.io/planner": "true"},
            "graph": {"name": "g", "implementation": "GENERATE_SERVER"},
        }],
    }))
    return store, ctl, dep


def test_planner_scale_down_vetoed_by_burn_page():
    """THE precedence regression: a page-severity burn verdict in the
    same tick vetoes the planner's scale-down at the actuation site —
    deterministically, counted, and it resets the shared streak."""
    store, ctl, dep = make_controller()
    pspec = dep.predictors[0]
    ctl._burn_verdicts[(dep.key, "p0")] = [
        {"slo": "ttft_p99", "severity": "page"}
    ]
    ctl._scale_down_streak[(dep.key, "p0")] = 2   # autoscaler's bank

    out = run(ctl._planner_actuate(
        dep, pspec, Decision("scale_down", "idle", rank=6)
    ))
    assert out == {"vetoed": True}
    assert ctl.planner_stats["vetoes"] == 1
    assert store.get("gdep").predictors[0].replicas == 2  # untouched
    # the shared hysteresis restarts: neither controller may downscale
    # off stale credit after a page
    assert (dep.key, "p0") not in ctl._scale_down_streak


def test_planner_scale_events_reset_autoscaler_streak():
    store, ctl, dep = make_controller()
    pspec = dep.predictors[0]
    ctl._scale_down_streak[(dep.key, "p0")] = 2
    out = run(ctl._planner_actuate(
        dep, pspec, Decision("scale_up", "warn burn", rank=4)
    ))
    assert out == {"replicas": 3}
    assert store.get("gdep").predictors[0].replicas == 3
    assert (dep.key, "p0") not in ctl._scale_down_streak
    assert ctl.planner_stats["scale_ups"] == 1


def test_planner_scale_down_applies_when_burn_quiet():
    store, ctl, dep = make_controller()
    out = run(ctl._planner_actuate(
        dep, dep.predictors[0], Decision("scale_down", "idle", rank=6)
    ))
    assert out == {"replicas": 1}
    assert store.get("gdep").predictors[0].replicas == 1


def test_planner_tick_once_closes_the_loop_on_page():
    """End to end through the controller: annotation parsed, verdicts
    consumed, decision actuated through the store (generation bump the
    reconcile loop would then roll out)."""
    store, ctl, dep = make_controller()
    ctl._burn_verdicts[(dep.key, "p0")] = [
        {"slo": "tpot_p99", "severity": "page"}
    ]
    results = run(ctl.planner_tick_once())
    ev = results[f"{dep.key}/p0"]
    assert ev["action"] == "scale_up" and ev["rank"] == 1
    assert ev["replicas"] == 3
    assert store.get("gdep").predictors[0].replicas == 3
    # dropping the annotation drops the planner state (no stale streaks)
    dep2 = store.get("gdep").clone()
    dep2.predictors[0].annotations = {}
    store.apply(dep2)
    run(ctl.planner_tick_once())
    assert ctl._planners == {}


def test_planner_annotations_strict():
    from seldon_core_tpu.graph.spec import (
        GraphSpecError, PredictorSpec, parse_planner_annotations,
    )

    def pspec(ann, impl="GENERATE_SERVER"):
        return PredictorSpec.from_dict({
            "name": "p", "annotations": ann,
            "graph": {"name": "g", "implementation": impl},
        })

    ok = parse_planner_annotations(
        pspec({"seldon.io/planner": "true",
               "seldon.io/planner-profile": "/tmp/x.spf1"})
    )
    assert ok == {"enabled": True, "profile": "/tmp/x.spf1"}
    assert parse_planner_annotations(pspec({})) is None
    with pytest.raises(GraphSpecError, match="true"):
        parse_planner_annotations(pspec({"seldon.io/planner": "yes"}))
    with pytest.raises(GraphSpecError, match="orphan"):
        parse_planner_annotations(
            pspec({"seldon.io/planner-profile": "/tmp/x.spf1"})
        )
    with pytest.raises(GraphSpecError, match="false"):
        parse_planner_annotations(
            pspec({"seldon.io/planner": "false",
                   "seldon.io/planner-profile": "/tmp/x.spf1"})
        )
    with pytest.raises(GraphSpecError, match="GENERATE_SERVER"):
        parse_planner_annotations(
            pspec({"seldon.io/planner": "true"}, impl="SIMPLE_MODEL")
        )


def test_planner_corrupt_profile_runs_model_less(tmp_path):
    """A corrupt SPF1 on disk refuses typed at load and DISABLES the
    cost model, never the planner — the burn/pressure rules still run."""
    store, ctl, dep = make_controller()
    p = tmp_path / "bad.spf1"
    p.write_bytes(b"SPF1garbage")
    key = (dep.key, "p0")
    planner = ctl._planner_for(key, {"enabled": True, "profile": str(p)})
    assert planner.cost_model is None
    assert planner.scale_down_ticks == ctl.scale_down_ticks  # shared
    # the good-profile path wires the model in
    good = tmp_path / "good.spf1"
    write_profile(str(good), profile(*GRID3))
    planner2 = ctl._planner_for(
        ("default/other", "p0"), {"enabled": True, "profile": str(good)}
    )
    assert planner2.cost_model is not None


# -- retune at a poll boundary: byte identity --------------------------------


from seldon_core_tpu.models.llm import DecoderLM  # noqa: E402
from seldon_core_tpu.serving.continuous import (  # noqa: E402
    ContinuousBatcher,
    RetuneError,
)

CFG = dict(
    vocab_size=256, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
    d_ff=64, max_seq=64, dtype="float32",
)
PROMPTS = [[3, 17, 42, 99, 7], [1, 2, 3], [9, 8, 7, 6], [5, 5, 5, 5, 5, 5]]
BUDGETS = [20, 7, 13, 9]


@pytest.fixture(scope="module")
def model_and_params():
    model = DecoderLM(**CFG)
    return model, model.init_params(0)


def make_batcher(model_and_params, **kw):
    model, params = model_and_params
    kw.setdefault("slots", 4)
    kw.setdefault("max_seq", 64)
    kw.setdefault("prefill_buckets", (8, 16, 32))
    kw.setdefault("steps_per_poll", 2)
    return ContinuousBatcher(model, params, **kw)


def run_batch(b, temperature=0.0):
    futures = [
        b.submit(p, max_new_tokens=m, temperature=temperature, seed=11 + i)
        for i, (p, m) in enumerate(zip(PROMPTS, BUDGETS))
    ]
    return [f.result(timeout=120) for f in futures]


@pytest.fixture(scope="module")
def references(model_and_params):
    b = make_batcher(model_and_params)          # step-at-a-time baseline
    try:
        return {"greedy": run_batch(b), "sampled": run_batch(b, 0.8)}
    finally:
        b.close()


def test_retune_applies_at_poll_boundary_byte_identical(
    model_and_params, references
):
    """Knobs retuned mid-run emit the SAME bytes as booting with them:
    greedy and seeded, across fused-K hops in both directions."""
    b = make_batcher(model_and_params, fused_steps_per_dispatch=8)
    try:
        assert run_batch(b) == references["greedy"]
        changed = b.retune(fused_steps_per_dispatch=2).result(timeout=30)
        assert changed == {"fused_steps_per_dispatch": [8, 2]}
        assert b.serving_config()["fused_steps_per_dispatch"] == 2
        assert run_batch(b) == references["greedy"]
        assert run_batch(b, 0.8) == references["sampled"]
        changed = b.retune(fused_steps_per_dispatch=8).result(timeout=30)
        assert changed == {"fused_steps_per_dispatch": [2, 8]}
        assert run_batch(b, 0.8) == references["sampled"]
        assert run_batch(b) == references["greedy"]
        assert b.stats["planner_retunes"] == 2
    finally:
        b.close()


def test_retune_under_in_flight_traffic_byte_identical(
    model_and_params, references
):
    """The poll-boundary contract under load: retune staged WHILE the
    batch is decoding still yields the reference bytes — the scheduler
    applies it between polls, never inside a burst."""
    b = make_batcher(model_and_params, fused_steps_per_dispatch=8)
    try:
        futures = [
            b.submit(p, max_new_tokens=m, seed=11 + i)
            for i, (p, m) in enumerate(zip(PROMPTS, BUDGETS))
        ]
        b.retune(fused_steps_per_dispatch=4).result(timeout=30)
        assert [f.result(timeout=120) for f in futures] \
            == references["greedy"]
        assert b.serving_config()["fused_steps_per_dispatch"] == 4
    finally:
        b.close()


def test_retune_out_of_census_refuses_typed(model_and_params):
    b = make_batcher(model_and_params, fused_steps_per_dispatch=8)
    try:
        with pytest.raises(RetuneError, match="census"):
            b.retune(fused_steps_per_dispatch=16)   # never warmed
        with pytest.raises(RetuneError, match="prefill_chunk"):
            b.retune(prefill_chunk=16)              # no chunk exes
        with pytest.raises(RetuneError, match="knob"):
            b.retune(slots=8)                       # boot-time only
        assert b.stats["planner_retunes"] == 0      # NOTHING staged
    finally:
        b.close()


def test_retune_flight_records_render_with_thrash_diagnosis(
    model_and_params,
):
    import sys

    sys.path.insert(0, "/root/repo/tools")
    try:
        from flight_report import diagnose
    finally:
        sys.path.pop(0)

    b = make_batcher(model_and_params, fused_steps_per_dispatch=8)
    try:
        b.retune(fused_steps_per_dispatch=2).result(timeout=30)
        b.retune(fused_steps_per_dispatch=8).result(timeout=30)  # revert!
        dump = b.flight.dump()
    finally:
        b.close()
    recs = [e for e in dump["entries"] if e.get("type") == "planner_retune"]
    assert len(recs) == 2
    assert all(r["origin"] == "planner" for r in recs)
    text = "\n".join(diagnose(dump))
    assert "planner retunes: 2 applied at poll boundaries" in text
    # a straight revert inside one window IS thrash — diagnosed
    assert "THRASHING" in text and "fused_steps_per_dispatch" in text


# -- fusion cost gate: must-flag / must-not-flag ------------------------------


def test_fusion_cost_gate_must_flag(monkeypatch):
    """A gate pricing compiles above any plausible savings SKIPS the
    segment — counted, flight-recorded — and the graph still serves
    hop-by-hop with byte-identical output."""
    from tests.test_fusion import REQ, chain_graph, make_executor, strip_puid

    from seldon_core_tpu.graph.engine_metrics import MetricsRegistry
    from tests.test_fusion import MatMul

    a, b = MatMul(0.1), MatMul(0.3, out=3)
    a.load(), b.load()
    monkeypatch.setenv("SELDON_FUSION_COST_GATE", json.dumps({
        "dispatch_floor_us": 50.0,
        "compile_cost_s": 10**9,
        "expected_dispatches": 1000,
    }))
    reg = MetricsRegistry()
    ex = make_executor(chain_graph("a", "b"), {"a": a, "b": b}, metrics=reg)
    assert not ex.fusion.segments
    assert reg.counter_total(
        "seldon_engine_fusion_skipped", {"unit": "a", "reason": "cost"}
    ) == 1.0
    recs = [e for e in ex.fusion.dump()["entries"]
            if e.get("type") == "fusion_skipped"]
    assert recs and recs[0]["segment"] == "a" and recs[0]["stages"] == 2

    monkeypatch.delenv("SELDON_FUSION_COST_GATE")
    ex_h = make_executor(chain_graph("a", "b"), {"a": a, "b": b},
                         fuse=False)
    assert strip_puid(run(ex.predict(dict(REQ)))) \
        == strip_puid(run(ex_h.predict(dict(REQ))))


def test_fusion_cost_gate_must_not_flag(monkeypatch):
    """The same gate with real volume compiles as always — zero skips.
    The gate prunes provably-bad compiles, it never taxes good ones."""
    from tests.test_fusion import MatMul, chain_graph, make_executor

    from seldon_core_tpu.graph.engine_metrics import MetricsRegistry

    a, b = MatMul(0.1), MatMul(0.3, out=3)
    a.load(), b.load()
    monkeypatch.setenv("SELDON_FUSION_COST_GATE", json.dumps({
        "dispatch_floor_us": 50.0,
        "compile_cost_s": 0.001,
        "expected_dispatches": 100_000,   # 1 hop * 50us * 1e5 = 5 s >> 1 ms
    }))
    reg = MetricsRegistry()
    ex = make_executor(chain_graph("a", "b"), {"a": a, "b": b}, metrics=reg)
    assert set(ex.fusion.segments) == {"a"}
    assert reg.counter_total(
        "seldon_engine_fusion_skipped", {"reason": "cost"}
    ) == 0.0


def test_fusion_gate_unpriced_gates_nothing():
    from seldon_core_tpu.graph.fusion import segment_worth_compiling

    assert segment_worth_compiling(5, {})
    assert segment_worth_compiling(5, {"dispatch_floor_us": 0,
                                       "expected_dispatches": 10**9})
    assert segment_worth_compiling(5, {"dispatch_floor_us": "junk"})
    # a 1-stage "segment" saves nothing: never worth a priced compile
    assert not segment_worth_compiling(1, {
        "dispatch_floor_us": 50.0, "compile_cost_s": 0.001,
        "expected_dispatches": 10**6,
    })
