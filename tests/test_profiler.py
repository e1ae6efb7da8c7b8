"""Device-time profiler + SLO burn engine (operate.md §4).

The load-bearing contracts: (1) the ledger attributes every warmed
dispatch per (kind, variant, tenant) — including under the full
composition of fused decode × chunked prefill × prefix splice, and under
pressure preemption/resume — (2) profiler on vs off is byte-identical
greedy AND seeded with an unchanged jit cache (the hooks wrap calls,
never args or results, and compile nothing), and (3) the burn engine
implements the two-window page rule (page only when BOTH windows burn)
over per-tenant error budgets. Fleet snapshot diff/merge semantics ride
here too: counters delta per member between scrapes, restarts fall back
to the fresh total, histograms merge bucketwise, quantiles never
average.
"""

import time

import pytest

from seldon_core_tpu.graph.engine_metrics import (
    MetricsRegistry,
    diff_fleet_snapshot,
)
from seldon_core_tpu.models.llm import DecoderLM
from seldon_core_tpu.resilience.faults import FaultInjector
from seldon_core_tpu.serving.continuous import ContinuousBatcher
from seldon_core_tpu.serving.profiler import KINDS, DeviceTimeLedger
from seldon_core_tpu.serving.slo_burn import (
    SEVERITIES,
    SloBurnEngine,
    SloObjective,
)

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


def run_batch(b, temperature=0.0, tenant=None):
    futures = [
        b.submit(p, max_new_tokens=m, temperature=temperature, seed=11 + i,
                 tenant=tenant)
        for i, (p, m) in enumerate(zip(PROMPTS, BUDGETS))
    ]
    return [f.result(timeout=120) for f in futures]


def ledger_kinds(prof):
    return {kind for (kind, _variant, _tenant) in prof.buckets()}


def jit_cache_size(b):
    """Total entries across every jitted executable the batcher holds —
    the pin that proves the profiler compiles nothing."""
    total = 0
    for name in dir(b):
        if name.startswith("__"):
            continue
        try:
            fn = getattr(b, name)
        except Exception:
            continue
        cache_size = getattr(fn, "_cache_size", None)
        if callable(cache_size):
            total += cache_size()
    return total


# -- ledger unit semantics ----------------------------------------------------


def test_ledger_disabled_is_noop():
    led = DeviceTimeLedger(enabled=False)
    with led.measure("prefill", variant="p32", bytes_read=10) as m:
        m.sync(None)
    assert led.buckets() == {}
    assert led.poll_flush() is None
    assert led.summary()["enabled"] is False


def test_ledger_attribution_and_flush_once():
    led = DeviceTimeLedger(enabled=True, hbm_gb_s=100.0)
    with led.measure("decode_burst", variant="b64", tenant="t1",
                     bytes_read=1000, tokens=8):
        pass
    with led.measure("decode_burst", variant="b64", tenant="t1",
                     bytes_read=1000, tokens=8):
        pass
    with led.measure("prefill", variant="p32", bytes_read=500, tokens=5):
        pass
    buckets = led.buckets()
    secs, n, nbytes, toks = buckets[("decode_burst", "b64", "t1")]
    assert n == 2 and nbytes == 2000 and toks == 16 and secs >= 0.0
    assert buckets[("prefill", "p32", "")][1] == 1
    # poll flush drains once: the same rows never ride two poll records
    rows = led.poll_flush()
    assert {r["kind"] for r in rows} == {"decode_burst", "prefill"}
    assert led.poll_flush() is None
    # cumulative buckets survive the flush (the /metrics view)
    assert led.buckets() == buckets
    gauges = led.gauges()
    assert 0.0 <= gauges["device_busy_frac"]
    assert "mbu_pct" in gauges  # hbm_gb_s configured


def test_ledger_rejects_unknown_kind():
    led = DeviceTimeLedger(enabled=True)
    with pytest.raises(ValueError):
        led.measure("not_a_kind")


# -- scheduler attribution under composition ----------------------------------


@pytest.fixture()
def _sub_tile_attn_buckets():
    old = ContinuousBatcher.MIN_ATTN_BUCKET
    ContinuousBatcher.MIN_ATTN_BUCKET = 16
    yield
    ContinuousBatcher.MIN_ATTN_BUCKET = old


def test_attribution_fused_chunked_prefix_splice(
    model_and_params, _sub_tile_attn_buckets
):
    """The full composition: fused decode × chunked prefill × prefix-cache
    splice, with tenant attribution — every dispatch lands in a typed
    (kind, variant, tenant) bucket and the variant vocabulary carries
    the realized K / bucket the executable was compiled for."""
    prof = DeviceTimeLedger(enabled=True, deep_every=4)
    b = make_batcher(
        model_and_params, attn_bucket=16, fused_steps_per_dispatch=8,
        prefill_chunk=16,
        prefill_buckets=(8, 16, 32, 48),
        prefix_cache_hbm_bytes=1 << 20, prefix_cache_min_tokens=4,
        profiler=prof,
    )
    try:
        run_batch(b, tenant="acme")
        kinds = ledger_kinds(prof)
        assert "prefill" in kinds
        assert "insert" in kinds
        assert "fused_burst" in kinds
        for kind, variant, tenant in prof.buckets():
            assert kind in KINDS
            if kind == "fused_burst":
                assert variant.startswith("k"), (kind, variant)
                assert tenant in ("", "acme")
        # a second long prompt sharing a chunk-aligned prefix rides the
        # radix cache through the CHUNKED admission path (suffix longer
        # than one chunk keeps it chunked): the donor slab splices in
        # instead of being recomputed
        b.generate([7] * 16, max_new_tokens=4)
        b.generate([7] * 16 + [9] * 17, max_new_tokens=4)
        kinds = ledger_kinds(prof)
        assert "splice" in kinds
        assert "chunk_prefill" in kinds
        s = prof.summary()
        assert s["enabled"] and s["device_time_s"] >= 0.0
        assert s["deep_samples"] > 0  # deep_every=4 actually sampled
        by_kind = s["by_kind"]
        assert set(by_kind) == ledger_kinds(prof)
    finally:
        b.close()


def _arm_shrink(b, lanes=1.3, after=4, restore=12):
    end = b.max_seq
    shrink = int(lanes * b._attn_need(end) * b._kv_key_bytes)
    inj = FaultInjector([], pressure={
        "shrink_to_bytes": shrink,
        "after_polls": b._work_poll_count + after,
        "restore_after_polls": restore,
    })
    b.pressure_hook = inj.pressure_hook()


def _wait_lanes(b, n, timeout=60.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if len(b._active) + len(b._chunked) >= n:
            return True
        time.sleep(0.002)
    return False


def test_preempt_resume_attributed_to_correct_buckets(model_and_params):
    """A pressure preemption's recompute-resume is not free — the ledger
    must show WHERE it went: the re-prefill + lane insert of the resumed
    request and the teacher-forced replay of its already-credited
    tokens, each in its own bucket (never smeared into decode_burst)."""
    prof = DeviceTimeLedger(enabled=True)
    b = make_batcher(model_and_params, hbm_ledger_bytes=1 << 40,
                     profiler=prof)
    try:
        futs = [
            b.submit(p, max_new_tokens=40, temperature=0.0) for p in PROMPTS
        ]
        assert _wait_lanes(b, 2)
        _arm_shrink(b, after=1)
        for f in futs:
            f.result(timeout=120)
        assert b.stats["preemptions"] >= 1
        assert b.stats["preempt_resumes"] == b.stats["preemptions"]
        kinds = ledger_kinds(prof)
        # the resume path: prefill over prompt+emitted, insert into a
        # lane, replay of the emitted tokens (k-step teacher forcing)
        assert {"prefill", "insert", "replay", "decode_burst"} <= kinds
        replay = [k for k in prof.buckets() if k[0] == "replay"]
        assert all(v.startswith("k") for _, v, _t in replay)
    finally:
        b.close()


# -- on/off byte-identity + jit-cache pin -------------------------------------


def test_profiler_on_off_byte_identical_and_no_new_executables(
    model_and_params,
):
    """The gate: profiler on emits byte-for-byte the profiler-off
    streams — greedy AND seeded — and the jit cache holds exactly the
    same number of compiled executables (the hooks wrap dispatch calls;
    they never touch args, results, or compilation)."""
    b_off = make_batcher(model_and_params, fused_steps_per_dispatch=8)
    try:
        greedy_ref = run_batch(b_off)
        sampled_ref = run_batch(b_off, temperature=0.8)
        cache_ref = jit_cache_size(b_off)
    finally:
        b_off.close()

    prof = DeviceTimeLedger(enabled=True, deep_every=3)
    b_on = make_batcher(model_and_params, fused_steps_per_dispatch=8,
                        profiler=prof)
    try:
        assert run_batch(b_on) == greedy_ref
        assert run_batch(b_on, temperature=0.8) == sampled_ref
        assert jit_cache_size(b_on) == cache_ref
        assert prof.buckets()  # it actually measured
        assert prof.summary()["deep_samples"] > 0
    finally:
        b_on.close()


def test_poll_records_carry_device_time_deltas(model_and_params):
    """Per-poll ledger deltas ride the flight recorder so a dump
    correlates device time with the scheduling decisions of the SAME
    poll window."""
    prof = DeviceTimeLedger(enabled=True)
    b = make_batcher(model_and_params, profiler=prof)
    try:
        run_batch(b)
        dump = b.flight.dump()
        rows = [
            r
            for e in dump["entries"]
            if e.get("type") == "poll"
            for r in e.get("device_time") or []
        ]
        assert rows, "no poll record carried device_time"
        assert {r["kind"] for r in rows} <= set(KINDS)
        assert all(r["n"] >= 1 for r in rows)
    finally:
        b.close()


# -- SLO burn engine ----------------------------------------------------------


def test_objective_validation():
    with pytest.raises(ValueError):
        SloObjective("ttft", threshold_s=0.2, target=1.0)  # no budget
    with pytest.raises(ValueError):
        SloObjective("ttft", threshold_s=0.0)
    obj = SloObjective("ttft", threshold_s=0.2, target=0.99)
    assert obj.budget == pytest.approx(0.01)


def test_burn_empty_window_burns_nothing():
    eng = SloBurnEngine([SloObjective("ttft", 0.2)])
    assert eng.verdicts() == []
    assert eng.worst() == "ok"


def test_burn_page_requires_both_windows():
    """The SRE two-window rule: a historical burn alone (slow window)
    must NOT page once the fast window has recovered — it downgrades to
    warn — while a sustained burn (both windows hot) pages."""
    eng = SloBurnEngine(
        [SloObjective("ttft", 0.2, target=0.99)],
        fast_window_s=0.05, slow_window_s=3600.0,
    )
    for _ in range(40):
        eng.observe("ttft", 0.5, tenant="a")  # breach
    (v,) = eng.verdicts()
    assert v["severity"] == "page" and v["fast_burn"] > 0
    # let the breaches age out of the fast window, then land good samples
    time.sleep(0.08)
    for _ in range(4):
        eng.observe("ttft", 0.01, tenant="a")
    (v,) = eng.verdicts()
    assert v["fast_burn"] == 0.0
    assert v["slow_burn"] > eng.warn_burn
    assert v["severity"] == "warn"
    assert 0.0 <= v["budget_remaining"] <= 1.0


def test_burn_per_tenant_isolation_and_counts():
    eng = SloBurnEngine([SloObjective("queue_wait", 0.05, target=0.99)])
    for _ in range(10):
        eng.observe("queue_wait", 0.5, tenant="hot")   # breach
        eng.observe("queue_wait", 0.001, tenant="cold")  # fine
    by_tenant = {v["tenant"]: v for v in eng.verdicts()}
    assert by_tenant["hot"]["severity"] == "page"
    assert by_tenant["cold"]["severity"] == "ok"
    # verdict counts are cumulative totals (the CounterDeltas contract):
    # a second evaluation grows them, never resets
    eng.verdicts()
    counts = eng.verdict_counts()
    assert counts[("hot", "queue_wait", "page")] == 2
    assert counts[("cold", "queue_wait", "ok")] == 2
    assert eng.worst() == "page"
    assert [SEVERITIES.index(s) for s in SEVERITIES] == [0, 1, 2]


def test_burn_unknown_slo_dropped():
    eng = SloBurnEngine([SloObjective("ttft", 0.2)])
    eng.observe("not_an_slo", 9.9)
    eng.observe("ttft", None)
    assert eng.verdicts() == []


# -- fleet snapshot merge semantics -------------------------------------------


def _registry_with(counter=0.0, seconds=None):
    reg = MetricsRegistry()
    if counter:
        reg.counter_inc("seldon_engine_device_dispatches",
                        {"kind": "prefill"}, counter)
    for s in seconds or []:
        reg.observe("seldon_engine_generate_ttft_seconds",
                    s, {"unit": "gen"})
    return reg


def test_fleet_diff_counters_and_restart_fallback():
    reg = _registry_with(counter=10.0)
    snap1 = reg.fleet_snapshot()
    reg.counter_inc("seldon_engine_device_dispatches",
                    {"kind": "prefill"}, 5.0)
    snap2 = reg.fleet_snapshot()
    d = diff_fleet_snapshot(snap1, snap2)
    (ent,) = d["counters"]["seldon_engine_device_dispatches"]
    assert ent["value"] == 5.0
    # member restart: totals reset below the previous capture — the diff
    # falls back to the fresh life's total instead of going negative
    fresh = _registry_with(counter=3.0).fleet_snapshot()
    d = diff_fleet_snapshot(snap2, fresh)
    (ent,) = d["counters"]["seldon_engine_device_dispatches"]
    assert ent["value"] == 3.0
    # no prior snapshot: the full current capture passes through
    assert diff_fleet_snapshot(None, snap1) is snap1


def test_fleet_ingest_merges_histograms_not_quantiles():
    """Two members' TTFT histograms merge bucketwise under per-member
    labels; the deployment-level quantile is computed from merged
    buckets — never an average of member p99s."""
    m1 = _registry_with(seconds=[0.01] * 9 + [2.0])
    m2 = _registry_with(seconds=[0.01] * 10)
    dep = MetricsRegistry()
    for i, m in enumerate((m1, m2)):
        dep.ingest_fleet(
            diff_fleet_snapshot(None, m.fleet_snapshot()),
            extra_labels={"member": f"m{i}", "deployment": "d"},
        )
    total = sum(
        dep.histogram_totals(
            "seldon_engine_generate_ttft_seconds", {"member": f"m{i}"}
        )[-1]
        for i in range(2)
    )
    assert total == 20
    text = dep.expose()
    assert 'member="m0"' in text and 'member="m1"' in text
    assert "seldon_engine_generate_ttft_seconds_bucket" in text
    # gauges overwrite per label set rather than adding
    g = MetricsRegistry()
    g.gauge_set("seldon_engine_mbu_pct", 40.0, {"unit": "gen"})
    dep.ingest_fleet(g.fleet_snapshot(), {"member": "m0"})
    g.gauge_set("seldon_engine_mbu_pct", 55.0, {"unit": "gen"})
    dep.ingest_fleet(g.fleet_snapshot(), {"member": "m0"})
    assert 'seldon_engine_mbu_pct{member="m0",unit="gen"} 55.0' in dep.expose()


def test_fleet_ingest_skips_mismatched_bucket_grid():
    m = _registry_with(seconds=[0.01])
    snap = m.fleet_snapshot()
    snap["buckets"] = [1, 2, 3]  # foreign grid cannot merge honestly
    dep = MetricsRegistry()
    dep.ingest_fleet(snap, {"member": "m0"})
    assert "seldon_engine_generate_ttft_seconds" not in dep.expose()
