"""Progressive-delivery subsystem tests (seldon_core_tpu/rollout/):
RolloutPlan parsing, the SLO-gated canary state machine incl. the
auto-rollback acceptance proof, shadow mirroring + divergence diffing,
and the live weight hot-swap path through the continuous batcher and
the generate server.
"""

import asyncio
import json
import time

import numpy as np
import pytest

from seldon_core_tpu.controlplane import (
    DeploymentController,
    ResourceStore,
    SeldonDeployment,
)
from seldon_core_tpu.controlplane.runtime import InProcessRuntime
from seldon_core_tpu.graph.engine_metrics import MetricsRegistry
from seldon_core_tpu.graph.spec import GraphSpecError, PredictorSpec, validate_deployment
from seldon_core_tpu.models.llm import DecoderLM
from seldon_core_tpu.rollout import (
    RolloutController,
    ShadowMirror,
    diff_responses,
    plan_from_deployment,
)
from seldon_core_tpu.rollout.controller import (
    ERRORS,
    PHASE_FAILED,
    PHASE_PROMOTED,
    PHASE_ROLLED_BACK,
    REQUESTS,
    TTFT_HIST,
)
from seldon_core_tpu.serving.continuous import ContinuousBatcher
from seldon_core_tpu.serving.prefix_cache import RadixPrefixIndex

CFG = dict(
    vocab_size=256,
    d_model=32,
    n_layers=2,
    n_heads=4,
    n_kv_heads=2,
    d_ff=64,
    max_seq=64,
    dtype="float32",
)


def run(coro):
    return asyncio.run(coro)


def rollout_dep(mode="canary", steps="25,100", interval="5", extra=None,
                candidate_traffic=0, name="dep"):
    """Two-predictor deployment: live baseline + annotated candidate."""
    ann = {"seldon.io/rollout": mode, "seldon.io/rollout-steps": steps,
           "seldon.io/rollout-interval-s": interval,
           "seldon.io/rollout-min-samples": "3", **(extra or {})}
    cand = {
        "name": "canary",
        "traffic": candidate_traffic,
        "annotations": ann,
        "graph": {"name": "clf", "implementation": "SIMPLE_MODEL"},
    }
    if mode == "shadow":
        cand["annotations"]["seldon.io/shadow"] = "true"
        cand["traffic"] = 0
    return SeldonDeployment.from_dict({
        "name": name,
        "predictors": [
            {"name": "baseline", "traffic": 100 - cand["traffic"],
             "graph": {"name": "clf", "implementation": "SIMPLE_MODEL"}},
            cand,
        ],
    })


# -- plan parsing ------------------------------------------------------------


def test_plan_defaults_and_parsing():
    dep = rollout_dep(steps="5,25,50,100", interval="30")
    plan = plan_from_deployment(dep)
    assert plan.mode == "canary"
    assert plan.candidate == "canary" and plan.baseline == "baseline"
    assert plan.steps == (5, 25, 50, 100)
    assert plan.interval_s == 30.0
    assert plan.min_samples == 3
    assert plan.max_error_delta == 0.05
    assert plan.max_ttft_ratio == 1.5 and plan.max_tpot_ratio == 1.5
    assert plan.max_latency_ratio is None
    assert plan.max_divergence == 0.0


def test_plan_shadow_steps_count_windows():
    """Shadow mode reads rollout-steps as the NUMBER of observation
    windows: a bare integer, or a weight list whose length counts."""
    plan = plan_from_deployment(rollout_dep(mode="shadow", steps="6"))
    assert len(plan.steps) == 6
    plan = plan_from_deployment(rollout_dep(mode="shadow", steps="5,25,100"))
    assert len(plan.steps) == 3
    with pytest.raises(GraphSpecError, match="observation window"):
        plan_from_deployment(rollout_dep(mode="shadow", steps="0"))


def test_plan_none_without_annotation():
    dep = rollout_dep()
    for p in dep.predictors:
        p.annotations.pop("seldon.io/rollout", None)
    assert plan_from_deployment(dep) is None


@pytest.mark.parametrize("steps", ["", "0,50", "50,25", "25,200", "a,b",
                                   "100"])
def test_plan_rejects_malformed_steps(steps):
    with pytest.raises(GraphSpecError):
        plan_from_deployment(rollout_dep(steps=steps))


def test_plan_rejects_bad_mode_and_gates():
    with pytest.raises(GraphSpecError, match="canary' or 'shadow"):
        plan_from_deployment(rollout_dep(mode="bluegreen"))
    with pytest.raises(GraphSpecError, match="rollout-interval-s"):
        plan_from_deployment(rollout_dep(interval="0"))
    with pytest.raises(GraphSpecError, match="rollout-max-ttft-ratio"):
        plan_from_deployment(
            rollout_dep(extra={"seldon.io/rollout-max-ttft-ratio": "fast"})
        )


def test_plan_shadow_mode_needs_shadow_annotation():
    dep = rollout_dep(mode="shadow")
    del dep.predictors[1].annotations["seldon.io/shadow"]
    with pytest.raises(GraphSpecError, match="seldon.io/shadow"):
        plan_from_deployment(dep)


def test_plan_canary_on_shadow_predictor_rejected():
    dep = rollout_dep(mode="canary")
    dep.predictors[1].annotations["seldon.io/shadow"] = "true"
    with pytest.raises(GraphSpecError, match="no routable traffic"):
        plan_from_deployment(dep)


def test_plan_needs_exactly_one_candidate_and_baseline():
    dep = rollout_dep()
    dep.predictors[0].annotations["seldon.io/rollout"] = "canary"
    with pytest.raises(GraphSpecError, match="at most one"):
        plan_from_deployment(dep)
    lonely = SeldonDeployment.from_dict({
        "name": "d",
        "predictors": [{
            "name": "only", "traffic": 100,
            "annotations": {"seldon.io/rollout": "canary"},
            "graph": {"name": "clf", "implementation": "SIMPLE_MODEL"},
        }],
    })
    with pytest.raises(GraphSpecError, match="exactly one live"):
        plan_from_deployment(lonely)


# -- spec validation (satellite: shadow + traffic is a manifest typo) --------


def test_shadow_predictor_with_traffic_rejected():
    preds = [
        PredictorSpec.from_dict({
            "name": "main", "traffic": 90,
            "graph": {"name": "clf", "implementation": "SIMPLE_MODEL"},
        }),
        PredictorSpec.from_dict({
            "name": "shadow", "traffic": 10,
            "annotations": {"seldon.io/shadow": "true"},
            "graph": {"name": "clf", "implementation": "SIMPLE_MODEL"},
        }),
    ]
    with pytest.raises(GraphSpecError, match="shadow predictor"):
        validate_deployment(preds)
    # zero-weight shadow stays valid (the supported shape)
    preds[1].traffic = 0
    preds[0].traffic = 100
    validate_deployment(preds)


def test_apply_time_rejects_malformed_rollout():
    """A typo'd rollout plan fails admission (validate_deployment, the
    reconciler/kube apply path) instead of silently idling at tick time."""
    bad = rollout_dep(steps="100,50")
    with pytest.raises(GraphSpecError, match="strictly increase"):
        validate_deployment(bad.predictors)
    bad = rollout_dep(extra={"seldon.io/rollout-max-ttft-ratio": "fast"})
    with pytest.raises(GraphSpecError, match="malformed"):
        validate_deployment(bad.predictors)
    # a well-formed plan passes, and so does a plain no-rollout spec
    validate_deployment(rollout_dep().predictors)
    plain = rollout_dep()
    plain.predictor("canary").annotations.clear()
    plain.predictor("canary").traffic = 0
    validate_deployment(plain.predictors)


# -- metrics label-subset readers --------------------------------------------


def test_registry_label_subset_readers():
    reg = MetricsRegistry()
    reg.counter_inc("c", {"deployment": "a", "unit": "m1"}, 2.0)
    reg.counter_inc("c", {"deployment": "a", "unit": "m2"}, 3.0)
    reg.counter_inc("c", {"deployment": "b"}, 7.0)
    assert reg.counter_total("c", {"deployment": "a"}) == 5.0
    assert reg.counter_total("c") == 12.0
    assert reg.counter_total("missing", {"deployment": "a"}) == 0.0
    reg.observe("h", 0.1, {"deployment": "a", "unit": "m1"})
    reg.observe("h", 0.3, {"deployment": "a", "unit": "m2"})
    s, n = reg.histogram_totals("h", {"deployment": "a"})
    assert n == 2 and s == pytest.approx(0.4)
    assert reg.histogram_totals("h", {"deployment": "x"}) == (0.0, 0.0)


# -- rollout controller state machine ----------------------------------------


class Clock:
    def __init__(self, t=1000.0):
        self.t = t

    def __call__(self):
        return self.t


def make_ctl(dep, reg=None):
    store = ResourceStore()
    store.apply(dep)
    clock = Clock()
    reg = reg or MetricsRegistry()
    return RolloutController(store, metrics=reg, now=clock), store, clock, reg


def feed(reg, name, requests=10, errors=0, ttft=None):
    reg.counter_inc(REQUESTS, {"deployment": name}, requests)
    if errors:
        reg.counter_inc(ERRORS, {"deployment": name}, errors)
    for t in ttft or []:
        reg.observe(TTFT_HIST, t, {"deployment": name})


def weights(store, name="dep"):
    dep = store.get(name)
    return {p.name: p.traffic for p in dep.predictors}


def test_canary_start_applies_first_step():
    ctl, store, clock, reg = make_ctl(rollout_dep(steps="25,100"))
    assert ctl.tick_all() == {"default/dep": "start"}
    assert weights(store) == {"baseline": 75, "canary": 25}
    st = ctl.state("default/dep")
    assert st.step_ix == 0
    assert [e["event"] for e in st.events] == ["start", "step"]
    # metrics exported
    out = reg.expose()
    assert "seldon_rollout_step" in out
    assert 'seldon_rollout_verdicts{deployment="default/dep",verdict="start"}' in out


def test_canary_promotes_through_steps_to_promoted():
    ctl, store, clock, reg = make_ctl(rollout_dep(steps="25,100", interval="5"))
    ctl.tick_all()
    # healthy traffic each analysis window, on both sides
    for expect_weights in ({"baseline": 0, "canary": 100},):
        feed(reg, "baseline", requests=20)
        feed(reg, "canary", requests=20)
        clock.t += 5.0
        assert ctl.tick_all() == {"default/dep": "promote"}
        assert weights(store) == expect_weights
    feed(reg, "baseline", requests=20)
    feed(reg, "canary", requests=20)
    clock.t += 5.0
    assert ctl.tick_all() == {"default/dep": "promoted"}
    assert ctl.state("default/dep").phase == PHASE_PROMOTED
    # a promoted rollout stays put
    clock.t += 5.0
    assert ctl.tick_all() == {}
    assert weights(store) == {"baseline": 0, "canary": 100}


def test_pause_on_insufficient_candidate_samples():
    ctl, store, clock, reg = make_ctl(rollout_dep(steps="25,100"))
    ctl.tick_all()
    feed(reg, "baseline", requests=50)  # candidate saw (almost) nothing
    feed(reg, "canary", requests=1)
    clock.t += 5.0
    assert ctl.tick_all() == {"default/dep": "pause"}
    # weights unchanged, still ramping at step 0
    assert weights(store) == {"baseline": 75, "canary": 25}
    assert ctl.state("default/dep").step_ix == 0


def test_error_rate_breach_rolls_back_within_one_interval():
    """The acceptance criterion: a gate breach restores baseline traffic
    in the SAME tick that detected it — i.e. within one analysis
    interval of the breach becoming observable."""
    ctl, store, clock, reg = make_ctl(rollout_dep(steps="25,100", interval="5"))
    ctl.tick_all()
    assert weights(store) == {"baseline": 75, "canary": 25}
    feed(reg, "baseline", requests=40, errors=0)
    feed(reg, "canary", requests=10, errors=5)  # 33% error rate
    clock.t += 5.0
    t_breach_observable = clock.t
    assert ctl.tick_all() == {"default/dep": "rollback"}
    # restored to the weights captured when the rollout began, and no
    # analysis interval elapsed between observation and restoration
    assert weights(store) == {"baseline": 100, "canary": 0}
    assert clock.t - t_breach_observable < 5.0
    st = ctl.state("default/dep")
    assert st.phase == PHASE_ROLLED_BACK
    trail = [e["event"] for e in st.events]
    assert trail == ["start", "step", "rollback"]
    assert st.events[-1]["restored"] == {"baseline": 100, "canary": 0}
    assert "error rate" in st.events[-1]["reasons"][0]
    assert 'verdict="rollback"' in reg.expose()
    # rolled-back is terminal: later healthy windows don't resurrect it
    feed(reg, "baseline", requests=20)
    feed(reg, "canary", requests=20)
    clock.t += 5.0
    assert ctl.tick_all() == {}
    assert weights(store) == {"baseline": 100, "canary": 0}


def test_ttft_ratio_breach_rolls_back():
    ctl, store, clock, reg = make_ctl(rollout_dep(steps="25,100"))
    ctl.tick_all()
    feed(reg, "baseline", requests=20, ttft=[0.1] * 10)
    feed(reg, "canary", requests=20, ttft=[0.3] * 10)  # 3x > default 1.5x
    clock.t += 5.0
    assert ctl.tick_all() == {"default/dep": "rollback"}
    assert weights(store) == {"baseline": 100, "canary": 0}
    assert "ttft" in ctl.state("default/dep").events[-1]["reasons"][0]


def test_ttft_gate_skipped_without_samples():
    """A predict-only graph (no TTFT series) must not trip or vacuously
    fail the generate gates."""
    ctl, store, clock, reg = make_ctl(rollout_dep(steps="25,100"))
    ctl.tick_all()
    feed(reg, "baseline", requests=20)
    feed(reg, "canary", requests=20)
    clock.t += 5.0
    assert ctl.tick_all() == {"default/dep": "promote"}


def test_shadow_rollout_promotes_then_fails_on_divergence():
    ctl, store, clock, reg = make_ctl(rollout_dep(mode="shadow", steps="25,100"))
    ctl.tick_all()
    # shadows carry no routed traffic: weights never move
    assert weights(store) == {"baseline": 100, "canary": 0}
    # mirror counters are deployment-scoped (mirror.py writes both labels;
    # the controller queries both so same-named predictors in another
    # deployment can't leak into this window)
    mlabels = {"deployment": "default/dep", "predictor": "canary"}
    reg.counter_inc("seldon_rollout_mirrors", mlabels, 10)
    clock.t += 5.0
    assert ctl.tick_all() == {"default/dep": "promote"}
    reg.counter_inc("seldon_rollout_mirrors", mlabels, 10)
    reg.counter_inc("seldon_rollout_divergence", mlabels, 2)
    clock.t += 5.0
    assert ctl.tick_all() == {"default/dep": "fail"}
    st = ctl.state("default/dep")
    assert st.phase == PHASE_FAILED
    assert "divergence" in st.events[-1]["reasons"][0]
    assert weights(store) == {"baseline": 100, "canary": 0}


def test_shadow_mirror_errors_fail_rollout():
    """A shadow that ERRORS every mirrored call never produces a
    'mirrored' sample — it must fail the rollout via the error gate, not
    pause forever below min_samples."""
    ctl, store, clock, reg = make_ctl(rollout_dep(mode="shadow", steps="25,100"))
    ctl.tick_all()
    mlabels = {"deployment": "default/dep", "predictor": "canary"}
    reg.counter_inc("seldon_rollout_mirror_errors", mlabels, 10)
    clock.t += 5.0
    assert ctl.tick_all() == {"default/dep": "fail"}
    st = ctl.state("default/dep")
    assert st.phase == PHASE_FAILED
    assert "mirror error rate" in st.events[-1]["reasons"][0]


def test_plan_edit_restarts_state_machine():
    ctl, store, clock, reg = make_ctl(rollout_dep(steps="25,100"))
    ctl.tick_all()
    feed(reg, "baseline", requests=20)
    feed(reg, "canary", requests=20)
    clock.t += 5.0
    ctl.tick_all()
    assert ctl.state("default/dep").step_ix == 1
    # operator edits the rollout: state machine restarts from step 0
    dep = store.get("dep").clone()
    dep.predictor("canary").annotations["seldon.io/rollout-steps"] = "10,100"
    store.apply(dep)
    assert ctl.tick_all() == {"default/dep": "start"}
    assert ctl.state("default/dep").step_ix == 0
    assert weights(store) == {"baseline": 90, "canary": 10}


def test_plan_edit_mid_ramp_keeps_pre_rollout_rollback_baseline():
    """An annotation edit restarts the ramp, but 'rollback' must still
    mean the weights from BEFORE the rollout ever moved them — not the
    mid-ramp split the edit happened to land on."""
    ctl, store, clock, reg = make_ctl(rollout_dep(steps="25,100"))
    ctl.tick_all()  # start: 75/25
    feed(reg, "baseline", requests=20)
    feed(reg, "canary", requests=20)
    clock.t += 5.0
    ctl.tick_all()  # promote: 0/100... mid-ramp at step 1
    dep = store.get("dep").clone()
    dep.predictor("canary").annotations["seldon.io/rollout-steps"] = "50,100"
    store.apply(dep)
    ctl.tick_all()  # restart at 50/50
    assert weights(store) == {"baseline": 50, "canary": 50}
    feed(reg, "baseline", requests=20)
    feed(reg, "canary", requests=10, errors=10)  # breach the error gate
    clock.t += 5.0
    assert ctl.tick_all() == {"default/dep": "rollback"}
    assert weights(store) == {"baseline": 100, "canary": 0}


def test_error_gate_skipped_when_baseline_idle():
    """The final window at step 100 leaves the baseline with no traffic:
    'no data' must not be read as '0% error rate' and roll back a
    candidate running its normal error rate."""
    ctl, store, clock, reg = make_ctl(rollout_dep(steps="25,100"))
    ctl.tick_all()
    feed(reg, "baseline", requests=20, errors=2)
    feed(reg, "canary", requests=18, errors=2)
    clock.t += 5.0
    assert ctl.tick_all() == {"default/dep": "promote"}  # now at 100%
    feed(reg, "canary", requests=18, errors=2)  # baseline: idle
    clock.t += 5.0
    assert ctl.tick_all() == {"default/dep": "promoted"}
    assert ctl.state("default/dep").phase == "promoted"


def test_capacity_failure_at_full_weight_rolls_back():
    """A canary healthy at partial traffic that falls over only under
    FULL load must still roll back in the final window — the gate
    compares against the last window in which the baseline served
    traffic, not a vacuous idle-baseline pass."""
    ctl, store, clock, reg = make_ctl(rollout_dep(steps="25,100"))
    ctl.tick_all()
    feed(reg, "baseline", requests=20)
    feed(reg, "canary", requests=20)  # healthy at 25%
    clock.t += 5.0
    assert ctl.tick_all() == {"default/dep": "promote"}  # now at 100%
    feed(reg, "canary", requests=2, errors=18)  # capacity collapse
    clock.t += 5.0
    assert ctl.tick_all() == {"default/dep": "rollback"}
    assert weights(store) == {"baseline": 100, "canary": 0}


def test_deleted_deployment_drops_state():
    ctl, store, clock, reg = make_ctl(rollout_dep())
    ctl.tick_all()
    assert ctl.state("default/dep") is not None
    store.delete("dep")
    ctl.tick_all()
    assert ctl.state("default/dep") is None


def test_rollout_state_survives_controller_restart():
    """A control-plane restart mid-ramp resumes from the status
    checkpoint — it must NOT re-start and capture the mid-ramp split as
    the 'pre-rollout' baseline, or a later breach would 'restore' the
    failing candidate's weights."""
    ctl, store, clock, reg = make_ctl(
        rollout_dep(steps="25,50,100", interval="5")
    )
    ctl.tick_all()  # start: 75/25
    feed(reg, "baseline", requests=20)
    feed(reg, "canary", requests=20)
    clock.t += 5.0
    assert ctl.tick_all() == {"default/dep": "promote"}
    assert weights(store) == {"baseline": 50, "canary": 50}
    # "restart": a fresh controller over the same store, cold in-memory state
    ctl2 = RolloutController(store, metrics=reg, now=clock)
    clock.t += 1.0
    assert ctl2.tick_all() == {}  # resumed mid-window: no verdict, no re-ramp
    st = ctl2.state("default/dep")
    assert st.step_ix == 1
    assert st.events[0]["event"] == "resume"
    assert weights(store) == {"baseline": 50, "canary": 50}
    feed(reg, "baseline", requests=20)
    feed(reg, "canary", requests=10, errors=10)  # breach the error gate
    clock.t += 5.0
    assert ctl2.tick_all() == {"default/dep": "rollback"}
    # the TRUE pre-rollout weights, not the 50/50 the restart found
    assert weights(store) == {"baseline": 100, "canary": 0}


def test_latency_regression_at_full_weight_rolls_back():
    """A canary whose TTFT regresses only under FULL load still rolls
    back: with the baseline idle in the final window, the gate compares
    against the remembered traffic-bearing baseline mean (same fallback
    the error gate has)."""
    ctl, store, clock, reg = make_ctl(rollout_dep(steps="25,100"))
    ctl.tick_all()
    feed(reg, "baseline", requests=20, ttft=[0.1] * 10)
    feed(reg, "canary", requests=20, ttft=[0.1] * 10)  # healthy at 25%
    clock.t += 5.0
    assert ctl.tick_all() == {"default/dep": "promote"}  # now at 100%
    feed(reg, "canary", requests=20, ttft=[0.5] * 10)  # 5x under full load
    clock.t += 5.0
    assert ctl.tick_all() == {"default/dep": "rollback"}
    assert "ttft" in ctl.state("default/dep").events[-1]["reasons"][0]
    assert weights(store) == {"baseline": 100, "canary": 0}


def test_capacity_failure_after_restart_still_rolls_back():
    """baseline_error_rate survives the checkpoint: a restart between
    the promote to 100% and the final analysis window must not turn the
    error gate vacuous (idle baseline) and promote a collapsing canary."""
    ctl, store, clock, reg = make_ctl(rollout_dep(steps="25,100"))
    ctl.tick_all()
    feed(reg, "baseline", requests=20)
    feed(reg, "canary", requests=20)
    clock.t += 5.0
    assert ctl.tick_all() == {"default/dep": "promote"}  # now at 100%
    ctl2 = RolloutController(store, metrics=reg, now=clock)
    ctl2.tick_all()  # rehydrates mid-window
    feed(reg, "canary", requests=2, errors=18)  # collapse under full load
    clock.t += 5.0
    assert ctl2.tick_all() == {"default/dep": "rollback"}
    assert weights(store) == {"baseline": 100, "canary": 0}


def test_promoted_rollout_stays_terminal_across_restart():
    ctl, store, clock, reg = make_ctl(rollout_dep(steps="25,100", interval="5"))
    ctl.tick_all()
    for _ in range(2):
        feed(reg, "baseline", requests=20)
        feed(reg, "canary", requests=20)
        clock.t += 5.0
        ctl.tick_all()
    assert ctl.state("default/dep").phase == PHASE_PROMOTED
    assert weights(store) == {"baseline": 0, "canary": 100}
    ctl2 = RolloutController(store, metrics=reg, now=clock)
    clock.t += 50.0
    assert ctl2.tick_all() == {}  # terminal: the ramp does not re-run
    assert ctl2.state("default/dep").phase == PHASE_PROMOTED
    assert weights(store) == {"baseline": 0, "canary": 100}
    # dropping the annotation clears the checkpoint
    plain = store.get("dep").clone()
    plain.predictor("canary").annotations.pop("seldon.io/rollout")
    store.apply(plain)
    ctl2.tick_all()
    assert store.get("dep").status.rollout is None


def test_invalid_plan_does_not_kill_other_rollouts():
    store = ResourceStore()
    bad = rollout_dep(steps="100,50", name="bad")
    good = rollout_dep(steps="25,100", name="good")
    store.apply(bad)
    store.apply(good)
    ctl = RolloutController(store, metrics=MetricsRegistry(), now=Clock())
    verdicts = ctl.tick_all()
    assert verdicts == {"default/good": "start"}


# -- divergence differ -------------------------------------------------------


def test_diff_generate_tokens():
    a = {"jsonData": {"tokens": [[1, 2, 3, 4]]}, "meta": {"puid": "x"}}
    b = {"jsonData": {"tokens": [[1, 2, 3, 4]]}, "meta": {"puid": "y"}}
    assert diff_responses(a, b) == {
        "kind": "generate", "diverged": False,
        "mismatch_tokens": 0, "first_mismatch": None,
    }
    c = {"jsonData": {"tokens": [[1, 2, 9, 4, 5]]}}
    v = diff_responses(a, c)
    assert v["diverged"] and v["kind"] == "generate"
    assert v["first_mismatch"] == 2 and v["mismatch_tokens"] >= 1


def test_diff_predict_numeric_tolerance():
    a = {"data": {"ndarray": [[1.0, 2.0]]}}
    close = {"data": {"ndarray": [[1.0 + 1e-7, 2.0]]}}
    far = {"data": {"ndarray": [[1.5, 2.0]]}}
    assert diff_responses(a, close)["diverged"] is False
    v = diff_responses(a, far)
    assert v["diverged"] and v["kind"] == "predict"
    assert v["max_abs_delta"] == pytest.approx(0.5)
    shaped = {"data": {"ndarray": [[1.0, 2.0], [3.0, 4.0]]}}
    assert diff_responses(a, shaped)["shape_mismatch"]


def test_diff_opaque_and_never_raises():
    assert diff_responses({"strData": "x"}, {"strData": "x"})["diverged"] is False
    assert diff_responses({"strData": "x"}, {"strData": "y"})["diverged"] is True
    # a malformed pair is a divergence, not an exception
    v = diff_responses({"jsonData": {"tokens": [[1]]}}, {"jsonData": {"tokens": "bad"}})
    assert v["diverged"] is True


# -- shadow mirror -----------------------------------------------------------


def test_mirror_diffs_and_counts():
    reg = MetricsRegistry()

    async def shadow(msg):
        return {"jsonData": {"tokens": [[1, 2, 99]]}}

    async def go():
        m = ShadowMirror([("canary", shadow)], deployment="default/dep",
                         metrics=reg)
        primary = {"jsonData": {"tokens": [[1, 2, 3]]}}
        assert m.submit({"jsonData": {}}, primary) == 1
        for _ in range(5):
            await asyncio.sleep(0.01)
        return m

    m = run(go())
    assert m.counts["mirrored"] == 1 and m.counts["diverged"] == 1
    assert len(m.recent) == 1 and m.recent[0]["predictor"] == "canary"
    assert reg.counter_total("seldon_rollout_divergence",
                             {"predictor": "canary"}) == 1.0
    assert reg.counter_total("seldon_rollout_mirrors") == 1.0


def test_mirror_bounded_concurrency_drops():
    gate = asyncio.Event()

    async def slow(msg):
        await gate.wait()
        return {"jsonData": {"tokens": [[1]]}}

    async def go():
        m = ShadowMirror([("s", slow)], max_concurrency=2)
        for _ in range(6):
            m.submit({}, {"jsonData": {"tokens": [[1]]}})
        assert m.counts["dropped"] == 4
        gate.set()
        for _ in range(5):
            await asyncio.sleep(0.01)
        return m

    m = run(go())
    assert m.counts["mirrored"] == 2
    assert m.inflight == 0


def test_mirror_failures_are_swallowed():
    async def boom(msg):
        raise RuntimeError("shadow died")

    async def go():
        m = ShadowMirror([("s", boom)])
        assert m.submit({}, {"jsonData": {"tokens": [[1]]}}) == 1
        for _ in range(5):
            await asyncio.sleep(0.01)
        return m

    m = run(go())
    assert m.counts["errors"] == 1 and m.counts["diverged"] == 0


def test_mirror_without_event_loop_drops_safely():
    m = ShadowMirror([("s", lambda msg: msg)])
    assert m.submit({}, {}) == 0
    assert m.counts["dropped"] == 1
    assert "recent_divergences" in m.summary()


# -- the gates read the engines' own counters -----------------------------------


@pytest.mark.parametrize("canary_fails,verdict,restored", [
    (False, "promote", {"baseline": 0, "canary": 100}),
    (True, "rollback", {"baseline": 100, "canary": 0}),
], ids=["healthy", "broken"])
def test_gates_read_what_the_engines_counted(canary_fails, verdict, restored):
    """No hand-fed registry: two engines on sockets, named as the
    predictors, count their own requests and errors under the label the
    controller reads. A healthy window promotes; a canary that answers
    500 is rolled back in the tick that sees it."""
    from _net import post_predictions

    from seldon_core_tpu.testing import EngineHarness
    from seldon_core_tpu.user_model import SeldonComponent

    class Unit(SeldonComponent):
        def __init__(self, fails):
            self.fails = fails

        def predict(self, X, names, meta=None):
            if self.fails:
                raise RuntimeError("canary is broken")
            return X

    def post(port):
        return post_predictions(port, b'{"data": {"ndarray": [[1.0]]}}')[0]

    ctl, store, clock, reg = make_ctl(rollout_dep(steps="50,100", interval="5"))
    baseline = EngineHarness(Unit(False), name="baseline", metrics=reg).start()
    canary = EngineHarness(Unit(canary_fails), name="canary", metrics=reg).start()
    try:
        assert ctl.tick_all() == {"default/dep": "start"}
        for _ in range(4):
            assert post(baseline.http_port) == 200
            assert (post(canary.http_port) >= 500) is canary_fails
        clock.t += 5.0
        assert ctl.tick_all() == {"default/dep": verdict}
        assert weights(store) == restored
    finally:
        baseline.stop()
        canary.stop()


# -- control-plane integration ----------------------------------------------


def test_canary_ramp_reroutes_without_restarting_engines():
    """A ramp step rewrites PredictorSpec.traffic only — component names
    exclude traffic, so the reconcile after a weight change must keep
    every running engine (re-route, not restart)."""

    async def go():
        store = ResourceStore()
        ctl = DeploymentController(store, runtime=InProcessRuntime(open_ports=False))
        ctl.rollout = RolloutController(store, metrics=MetricsRegistry(),
                                        now=Clock())
        dep = rollout_dep(steps="25,100")
        store.apply(dep)
        await ctl.reconcile(dep.clone())
        before = dict(ctl.components)
        assert ctl.rollout.tick_all() == {"default/dep": "start"}
        updated = store.get("dep")
        assert {p.name: p.traffic for p in updated.predictors} == {
            "baseline": 75, "canary": 25,
        }
        await ctl.reconcile(updated.clone())
        after = dict(ctl.components)
        assert set(after) == set(before)
        for name in after:
            assert after[name][0] is before[name][0], name  # same handle
        await ctl.shutdown()

    run(go())


def test_reconciler_wires_and_clears_shadow_mirrors():
    async def go():
        store = ResourceStore()
        ctl = DeploymentController(store, runtime=InProcessRuntime(open_ports=False))
        dep = rollout_dep(mode="shadow")
        store.apply(dep)
        await ctl.reconcile(dep.clone())
        by_pred = {
            h.spec.predictor: h
            for h, _ in ctl.components.values()
        }
        assert by_pred["baseline"].app.shadow_mirror is not None
        assert by_pred["canary"].app.shadow_mirror is None
        mirror = by_pred["baseline"].app.shadow_mirror
        assert [n for n, _ in mirror.targets] == ["canary"]
        # a mirrored predict diffs identical graphs as non-divergent
        out = await by_pred["baseline"].app.predict(
            {"data": {"ndarray": [[1.0, 2.0]]}}
        )
        for _ in range(10):
            await asyncio.sleep(0.01)
        assert mirror.counts["mirrored"] == 1
        assert mirror.counts["diverged"] == 0
        assert out["data"]
        # dropping the rollout annotation clears the mirror (byte-identical
        # no-rollout path restored)
        plain = store.get("dep").clone()
        plain.predictor("canary").annotations.pop("seldon.io/rollout")
        store.apply(plain)
        await ctl.reconcile(plain.clone())
        by_pred = {
            h.spec.predictor: h for h, _ in ctl.components.values()
        }
        assert by_pred["baseline"].app.shadow_mirror is None
        await ctl.shutdown()

    run(go())


def test_terminal_shadow_rollout_unwires_mirror():
    """A failed (or promoted) shadow rollout is no longer active: the
    mirror must come off even though the annotations are still on the
    spec, whether the terminal phase lives in memory or only in the
    status checkpoint (control-plane restart)."""
    async def go():
        from seldon_core_tpu.rollout.controller import plan_signature

        store = ResourceStore()
        ctl = DeploymentController(
            store, runtime=InProcessRuntime(open_ports=False)
        )
        dep = rollout_dep(mode="shadow")
        store.apply(dep)
        await ctl.reconcile(dep.clone())

        def baseline_app():
            return {
                h.spec.predictor: h for h, _ in ctl.components.values()
            }["baseline"].app

        assert baseline_app().shadow_mirror is not None
        # in-memory terminal phase unwires (the manager loop calls
        # _wire_shadow_mirrors right after a tick verdict)
        ctl.rollout.tick_all()  # start
        st = ctl.rollout.state("default/dep")
        st.phase = PHASE_FAILED
        ctl._wire_shadow_mirrors(store.get("dep"))
        assert baseline_app().shadow_mirror is None
        # restart path: cold state machine, terminal checkpoint only
        ctl.rollout._states.clear()
        store.get("dep").status.rollout = None
        ctl._wire_shadow_mirrors(store.get("dep"))
        assert baseline_app().shadow_mirror is not None  # active again
        store.get("dep").status.rollout = {
            "plan_sig": plan_signature(plan_from_deployment(store.get("dep"))),
            "phase": PHASE_FAILED, "step_ix": 0, "baseline_weights": {},
        }
        ctl.rollout._states.clear()
        ctl._wire_shadow_mirrors(store.get("dep"))
        assert baseline_app().shadow_mirror is None
        await ctl.shutdown()

    run(go())


def test_gateway_feedback_still_mirrors_during_shadow_rollout():
    """The engine's ShadowMirror covers PREDICTIONS only — the gateway
    must keep fanning feedback out to shadows mid-rollout (reward
    signals a shadow's routers need), while skipping its legacy
    prediction mirror (the engine now owns that, diffed and bounded)."""
    async def go():
        from seldon_core_tpu.controlplane import Gateway
        from seldon_core_tpu.http_server import Request

        store = ResourceStore()
        gw = Gateway(seed=0)
        ctl = DeploymentController(
            store, runtime=InProcessRuntime(open_ports=False), gateway=gw
        )
        dep = rollout_dep(mode="shadow")
        store.apply(dep)
        await ctl.reconcile(dep.clone())
        calls = []
        real_forward = gw._forward

        async def spy(handle, path, payload):
            calls.append((handle.spec.predictor, path))
            return await real_forward(handle, path, payload)

        gw._forward = spy
        app = gw.app()
        body = json.dumps({"data": {"ndarray": [[1.0, 2.0]]}}).encode()
        req = Request("POST", "/seldon/default/dep/api/v0.1/predictions", "",
                      {"content-type": "application/json"}, body)
        resp = await app._dispatch(req)
        assert resp.status == 200
        # no legacy gateway mirror for predictions: the engine mirrors those
        assert [c for c in calls if c[0] == "canary"] == []
        fb = json.dumps({
            "response": {"data": {"ndarray": [[1.0, 2.0]]}}, "reward": 1.0,
        }).encode()
        req = Request("POST", "/seldon/default/dep/api/v0.1/feedback", "",
                      {"content-type": "application/json"}, fb)
        resp = await app._dispatch(req)
        assert resp.status == 200
        for _ in range(20):
            if ("canary", "/api/v0.1/feedback") in calls:
                break
            await asyncio.sleep(0.01)
        assert ("canary", "/api/v0.1/feedback") in calls
        await ctl.shutdown()

    run(go())


# -- live weight hot-swap ----------------------------------------------------


@pytest.fixture(scope="module")
def model_and_params():
    model = DecoderLM(**CFG)
    return model, model.init_params(0)


def test_weight_swap_identical_params_byte_identical(model_and_params):
    model, params = model_and_params
    b = ContinuousBatcher(model, params, slots=2, max_seq=64,
                          prefill_buckets=(8,))
    try:
        prompt = [3, 17, 42, 99, 7]
        before = b.generate(prompt, max_new_tokens=8)
        fut = b.request_weight_swap(model.init_params(0), version="v1")
        assert fut.result(timeout=30.0) == "v1"
        assert b.weight_version == "v1"
        assert b.stats["weight_swaps"] == 1
        after = b.generate(prompt, max_new_tokens=8)
        assert after == before
        # flight recorder carries the swap event with drain attribution
        entries = b.flight.dump(10_000)["entries"]
        swaps = [e for e in entries if e.get("type") == "weight_swap"]
        assert len(swaps) == 1
        assert swaps[0]["old_version"] == 0
        assert swaps[0]["new_version"] == "v1"
        assert swaps[0]["drained_lanes"] == 0
    finally:
        b.close()


def test_weight_swap_drains_in_flight_lanes(model_and_params):
    """Requests in flight when the swap is staged finish (on the old
    weights) with the exact greedy outputs; queued admissions resume on
    the new version; the swap future resolves."""
    model, params = model_and_params
    b = ContinuousBatcher(model, params, slots=2, max_seq=64,
                          prefill_buckets=(8,))
    try:
        rng = np.random.RandomState(0)
        prompts = [rng.randint(0, 256, 5).tolist() for _ in range(4)]
        expected = [b.generate(p, max_new_tokens=6) for p in prompts]
        futs = [b.submit(p, max_new_tokens=6) for p in prompts]
        swap_fut = b.request_weight_swap(model.init_params(0))
        got = [f.result(timeout=30.0) for f in futs]
        assert got == expected
        assert swap_fut.result(timeout=30.0) == 1  # auto-assigned version
        assert b.stats["weight_swaps"] == 1
        # drained in-flight lanes are attributed on the recorder event
        swaps = [e for e in b.flight.dump(10_000)["entries"]
                 if e.get("type") == "weight_swap"]
        assert len(swaps) == 1
    finally:
        b.close()


def test_weight_swap_cancel_resumes_admissions(model_and_params):
    """cancel_weight_swap aborts a staged swap (future raises, version
    unchanged) and admissions resume — the escape hatch for a drain that
    cannot converge."""
    model, params = model_and_params
    b = ContinuousBatcher(model, params, slots=2, max_seq=64,
                          prefill_buckets=(8,))
    try:
        prompt = [3, 17, 42, 99, 7]
        before = b.generate(prompt, max_new_tokens=6)
        # keep a lane busy so the staged swap holds the drain open
        slow = b.submit([9, 8, 7, 6, 5], max_new_tokens=24)
        fut = b.request_weight_swap(model.init_params(0), version="v9")
        assert b.swap_pending() is True
        assert b.cancel_weight_swap() is True
        assert b.swap_pending() is False
        assert b.cancel_weight_swap() is False  # nothing staged anymore
        with pytest.raises(RuntimeError, match="cancelled"):
            fut.result(timeout=10.0)
        slow.result(timeout=30.0)
        # no flip happened, and new admissions serve on the old version
        assert b.weight_version == 0
        assert b.stats["weight_swaps"] == 0
        assert b.generate(prompt, max_new_tokens=6) == before
        # a later swap still lands
        assert b.request_weight_swap(model.init_params(0)).result(30.0) == 1
    finally:
        b.close()


def test_weight_swap_rejects_current_version(model_and_params):
    """Re-using the served version id would leave version-keyed prefix
    slabs from the OLD weights valid under the new ones — the exact
    stale-K/V splice the keying exists to prevent."""
    model, params = model_and_params
    b = ContinuousBatcher(model, params, slots=2, max_seq=64,
                          prefill_buckets=(8,))
    try:
        assert b.request_weight_swap(model.init_params(0), version="v1") \
            .result(30.0) == "v1"
        with pytest.raises(ValueError, match="already the served version"):
            b.request_weight_swap(model.init_params(0), version="v1")
        # the auto-sequence skips a collision with the served version too
        b2 = ContinuousBatcher(model, params, slots=2, max_seq=64,
                               prefill_buckets=(8,))
        try:
            assert b2.request_weight_swap(
                model.init_params(0), version=1).result(30.0) == 1
            assert b2.request_weight_swap(
                model.init_params(0)).result(30.0) == 2
        finally:
            b2.close()
    finally:
        b.close()


def test_weight_swap_rejects_incompatible_params(model_and_params):
    model, params = model_and_params
    b = ContinuousBatcher(model, params, slots=2, max_seq=64,
                          prefill_buckets=(8,))
    try:
        other = DecoderLM(**{**CFG, "d_model": 16, "n_heads": 2}).init_params(0)
        with pytest.raises(ValueError, match="rejected"):
            b.request_weight_swap(other)
        assert b.stats["weight_swaps"] == 0
        with b._swap_lock:
            assert b._pending_swap is None
        # a second (valid) swap still works after the rejection
        assert b.request_weight_swap(model.init_params(0)).result(30.0) == 1
    finally:
        b.close()


def test_weight_swap_rejected_under_speculation(model_and_params):
    model, params = model_and_params
    draft = DecoderLM(
        vocab_size=CFG["vocab_size"], d_model=16, n_layers=1, n_heads=2,
        n_kv_heads=1, d_ff=32, max_seq=64, dtype="float32",
    )
    b = ContinuousBatcher(model, params, slots=2, max_seq=64,
                          prefill_buckets=(8,), speculate_tokens=2,
                          draft_model=draft, draft_params=draft.init_params(9))
    try:
        with pytest.raises(RuntimeError, match="speculative"):
            b.request_weight_swap(model.init_params(0))
    finally:
        b.close()


def test_close_fails_pending_swap(model_and_params):
    model, params = model_and_params
    b = ContinuousBatcher(model, params, slots=2, max_seq=64,
                          prefill_buckets=(8,))
    b.close()
    with pytest.raises(RuntimeError, match="closed"):
        b.request_weight_swap(model.init_params(0))


def test_weight_swap_purges_prefix_cache(model_and_params):
    model, params = model_and_params
    b = ContinuousBatcher(model, params, slots=2, max_seq=64,
                          prefill_buckets=(8, 16),
                          prefix_cache_hbm_bytes=64 << 20,
                          prefix_cache_min_tokens=4)
    try:
        prompt = list(range(1, 13))
        first = b.generate(prompt, max_new_tokens=6)
        assert b.stats["prefix_cache_bytes"] > 0
        evicted_before = b.stats["prefix_evicted"]
        b.request_weight_swap(model.init_params(0)).result(timeout=30.0)
        # every old-weights slab purged: stale K/V can never splice into a
        # new-weights prefill
        assert b._prefix_index.slab_count == 0
        assert b._prefix_index.version == 1
        assert b.stats["prefix_evicted"] > evicted_before
        assert b.stats["prefix_cache_bytes"] == 0
        # identical weights: the re-primed pool serves identical bytes
        again = b.generate(prompt, max_new_tokens=6)
        assert again == first
    finally:
        b.close()


def test_prefix_index_set_version_purges_and_rekeys():
    idx = RadixPrefixIndex(1 << 20)
    toks = (1, 2, 3, 4)
    idx.insert(toks, slab="old-kv", nbytes=100)
    assert idx.match(toks) == (4, "old-kv")
    assert idx.set_version("v1") == 1
    assert idx.slab_count == 0 and idx.total_bytes == 0
    assert idx.match(toks) == (0, None)
    # same version again is a no-op; new inserts key to the new version
    assert idx.set_version("v1") == 0
    idx.insert(toks, slab="new-kv", nbytes=100)
    assert idx.match(toks) == (4, "new-kv")


# -- generate server + engine route -----------------------------------------


def _tiny_model_dir(root):
    from seldon_core_tpu.testing import write_model_dir

    return write_model_dir(str(root), "llm", {
        "vocab_size": 256, "d_model": 32, "n_layers": 2, "n_heads": 2,
        "n_kv_heads": 2, "d_ff": 64, "max_seq": 64,
    })


def test_generateserver_hot_swap_rejects_then_swaps(tmp_path):
    """One served component, both hot_swap outcomes: a different-arch
    checkpoint is rejected without touching serving, then the same
    checkpoint swaps in byte-identically."""
    from seldon_core_tpu.testing import write_model_dir
    from seldon_core_tpu.servers.generateserver import GenerateServer

    model_dir = _tiny_model_dir(tmp_path)
    other_dir = write_model_dir(str(tmp_path / "other"), "llm", {
        "vocab_size": 256, "d_model": 16, "n_layers": 2, "n_heads": 2,
        "n_kv_heads": 2, "d_ff": 32, "max_seq": 64,
    })
    component = GenerateServer(model_uri=model_dir, slots=2, steps_per_poll=4)
    component.load()
    try:
        req = {"prompt_tokens": [[1, 2, 3, 4, 5]], "max_new_tokens": 6,
               "temperature": 0.0}
        before = component.predict(dict(req), [])["tokens"]
        with pytest.raises(ValueError, match="architecture differs"):
            component.hot_swap(other_dir)
        # serving unaffected by the rejected swap
        assert component.predict(dict(req), [])["tokens"] == before
        assert component.batcher.weight_version == 0
        out = component.hot_swap(model_dir, wait_s=30.0)
        assert out["swapped"] is True
        assert out["version"] == "v1" == out["weight_version"]
        after = component.predict(dict(req), [])["tokens"]
        assert after == before  # same checkpoint == byte-identical
        # metrics ship the swap count as a delta counter
        keys = {m["key"] for m in component.metrics()}
        assert "gen_weight_swaps" in keys
    finally:
        component.batcher.close()


def test_engine_weights_swap_route(tmp_path):
    import http.client

    from seldon_core_tpu.testing import EngineHarness
    from seldon_core_tpu.servers.generateserver import GenerateServer

    model_dir = _tiny_model_dir(tmp_path)
    component = GenerateServer(model_uri=model_dir, slots=2, steps_per_poll=4)
    component.load()
    harness = EngineHarness(component, name="swap-test").start()
    try:
        conn = http.client.HTTPConnection("127.0.0.1", harness.http_port)
        gen_body = json.dumps({"jsonData": {
            "prompt_tokens": [[1, 2, 3, 4]], "max_new_tokens": 5,
            "temperature": 0.0,
        }}).encode()
        conn.request("POST", "/api/v0.1/predictions", gen_body,
                     {"Content-Type": "application/json"})
        before = json.loads(conn.getresponse().read())["jsonData"]["tokens"]

        conn.request("POST", "/weights/swap",
                     json.dumps({"model_uri": model_dir}).encode(),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        payload = json.loads(resp.read())
        assert resp.status == 200, payload
        assert payload["units"]["model"]["swapped"] is True

        # serving continues, byte-identical (same checkpoint)
        conn.request("POST", "/api/v0.1/predictions", gen_body,
                     {"Content-Type": "application/json"})
        after = json.loads(conn.getresponse().read())["jsonData"]["tokens"]
        assert after == before

        # missing model_uri is a 400, not a crash
        conn.request("POST", "/weights/swap", b"{}",
                     {"Content-Type": "application/json"})
        assert conn.getresponse().read() and True

        # {"cancel": true} with nothing staged reports cancelled: false
        conn.request("POST", "/weights/swap",
                     json.dumps({"cancel": True}).encode(),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        payload = json.loads(resp.read())
        assert resp.status == 200, payload
        assert payload["units"]["model"]["cancelled"] is False
    finally:
        harness.stop()
        component.batcher.close()


def test_engine_weights_swap_route_501_without_support():
    import http.client

    from seldon_core_tpu.testing import EngineHarness
    from seldon_core_tpu.user_model import SeldonComponent

    class Plain(SeldonComponent):
        def predict(self, X, names, meta=None):
            return X

    harness = EngineHarness(Plain(), name="no-swap").start()
    try:
        conn = http.client.HTTPConnection("127.0.0.1", harness.http_port)
        conn.request("POST", "/weights/swap",
                     json.dumps({"model_uri": "/nope"}).encode(),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        resp.read()
        assert resp.status == 501
    finally:
        harness.stop()
