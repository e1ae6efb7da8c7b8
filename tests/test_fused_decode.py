"""Fused multi-step on-device decode (the dispatch-floor killer).

The load-bearing contract: with ``fused_steps_per_dispatch`` on, one
dispatch runs up to K decode steps entirely on device — per-step KV
append, greedy + seeded-categorical sampling, stop-token detection, and
per-lane done masks that freeze finished lanes — and greedy AND
seeded-sampling outputs stay byte-identical to the step-at-a-time path
under every composition: prefix-cache splice, chunked prefill
interleave, lanes at mixed depths, mid-burst stops at every position in K,
pressure-triggered preemption at a fused poll boundary, and drain
checkpointing mid-run. Speculation degrades the fused path to the spec
burst (which fuses draft/verify its own way).
"""

import json
import time

import numpy as np
import pytest

from seldon_core_tpu.models.llm import DecoderLM
from seldon_core_tpu.resilience.faults import FaultInjector
from seldon_core_tpu.serving.continuous import ContinuousBatcher

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
BUDGETS = [20, 7, 13, 9]  # staggered so adaptive K must shrink


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
    """Step-at-a-time outputs (fused off): greedy + seeded, concurrent."""
    b = make_batcher(model_and_params)
    try:
        greedy = run_batch(b)
        sampled = run_batch(b, temperature=0.8)
        # eos references: the greedy continuation of PROMPTS[0]
        long = b.generate(PROMPTS[0], max_new_tokens=16)
        eos_refs = {}
        for j in range(8):
            eos = long[len(PROMPTS[0]) + j]
            eos_refs[j] = b.generate(
                PROMPTS[0], max_new_tokens=16, eos_id=eos
            )
    finally:
        b.close()
    return {"greedy": greedy, "sampled": sampled, "eos": eos_refs}


# -- core byte-identity -------------------------------------------------------


def test_fused_greedy_and_seeded_identical(model_and_params, references):
    """Concurrent mixed-budget batch: fused on (K=16 over a 2-step poll)
    emits byte-for-byte the step-at-a-time scheduler's streams, greedy
    AND seeded, while actually fusing (many steps per dispatch)."""
    b = make_batcher(model_and_params, fused_steps_per_dispatch=16)
    try:
        assert run_batch(b) == references["greedy"]
        assert run_batch(b, temperature=0.8) == references["sampled"]
        assert b.stats["fused_dispatches"] > 0
        # the whole point: more device steps than host dispatches
        assert b.stats["fused_steps"] > b.stats["fused_dispatches"]
    finally:
        b.close()


def test_fused_eos_at_every_burst_position(model_and_params, references):
    """On-device stop detection: an eos landing at EVERY position within
    the fused burst stops the stream exactly where the step-at-a-time
    path stops it — no overshoot token ever credited."""
    b = make_batcher(model_and_params, fused_steps_per_dispatch=8)
    try:
        for j, expected in references["eos"].items():
            got = b.generate(
                PROMPTS[0], max_new_tokens=16, eos_id=expected[-1]
            )
            assert got == expected, f"eos at burst position {j}"
    finally:
        b.close()


def test_fused_with_prefix_cache_splice(model_and_params):
    """Prefix-cache hits splice a donor slab under the fused path and the
    output equals the step-at-a-time path's over the SAME splice. The
    contract is fused-on vs fused-off, warm-hit vs warm-hit — NOT vs a
    cold whole-prompt forward, whose different executable can flip
    near-tied argmaxes on toy models."""
    rng = np.random.RandomState(23)
    shared = rng.randint(0, 256, 20).tolist()
    prompts = [shared + rng.randint(0, 256, t).tolist() for t in (4, 6, 3)]
    cache_kw = dict(
        prefix_cache_hbm_bytes=1 << 26, prefix_cache_min_tokens=4,
    )
    fused = make_batcher(
        model_and_params, slots=2, fused_steps_per_dispatch=16, **cache_kw
    )
    plain = make_batcher(model_and_params, slots=2, **cache_kw)
    try:
        for p in prompts:
            assert fused.generate(p, max_new_tokens=6) == \
                plain.generate(p, max_new_tokens=6)
        assert fused.stats["prefix_hits"] >= 2
        assert plain.stats["prefix_hits"] >= 2
        assert fused.stats["fused_dispatches"] > 0
        assert plain.stats["fused_dispatches"] == 0
    finally:
        fused.close()
        plain.close()


def test_fused_with_chunked_prefill_at_mixed_depths(model_and_params,
                                                    references,
                                                    _sub_tile_attn_buckets):
    """Chunked prefill interleave composes with the fused path while
    lanes sit in different attention buckets: same bytes, fused
    dispatches actually run."""
    b = make_batcher(
        model_and_params, attn_bucket=16, fused_steps_per_dispatch=16,
        prefill_chunk=16,
    )
    try:
        futures = []
        for i, (p, m) in enumerate(zip(PROMPTS, BUDGETS)):
            futures.append(b.submit(p, max_new_tokens=m))
            if i % 2 == 1:
                time.sleep(0.03)  # stagger so depths genuinely mix
        got = [f.result(timeout=120) for f in futures]
        assert got == references["greedy"]
        assert b.stats["fused_dispatches"] > 0
    finally:
        b.close()
    # long prompt through the staging-slab chunked path, fused decode
    b = make_batcher(
        model_and_params, slots=2, fused_steps_per_dispatch=16,
        prefill_chunk=16,
    )
    try:
        import jax.numpy as jnp

        model, params = model_and_params
        p = list(range(1, 30))
        got = b.generate(p, max_new_tokens=8)
        exp = np.asarray(
            model.generate(params, jnp.asarray([p], jnp.int32), 8)
        )[0].tolist()
        assert got == exp
        assert b.stats["prefill_chunks"] > 0
    finally:
        b.close()


@pytest.fixture()
def _sub_tile_attn_buckets():
    old = ContinuousBatcher.MIN_ATTN_BUCKET
    ContinuousBatcher.MIN_ATTN_BUCKET = 16
    yield
    ContinuousBatcher.MIN_ATTN_BUCKET = old


# -- pressure / drain boundaries ---------------------------------------------


def test_fused_pressure_preemption_at_poll_boundary(model_and_params,
                                                    references):
    """A mid-run HBM-ledger shrink preempts decode lanes at a fused poll
    boundary; every request still completes byte-identically (greedy AND
    seeded — recompute-resume continues the exact stream), and the
    adaptive K records the pressure shrink in the flight recorder."""
    b = make_batcher(
        model_and_params, fused_steps_per_dispatch=16,
        hbm_ledger_bytes=1 << 40,
    )
    shrink = int(1.3 * b._attn_need(b.max_seq) * b._kv_key_bytes)
    inj = FaultInjector([], pressure={
        "shrink_to_bytes": shrink,
        "after_polls": b._work_poll_count + 4,
        "restore_after_polls": 12,
    })
    b.pressure_hook = inj.pressure_hook()
    try:
        assert run_batch(b) == references["greedy"]
        assert b.stats["preemptions"] >= 1
        assert b.stats["preempt_resumes"] >= 1
        plans = [
            e["plan"] for e in b.flight.dump()["entries"]
            if e.get("type") == "poll" and "plan" in e
        ]
        assert any(p.get("mode") == "fused" for p in plans)
    finally:
        b.close()
    # K floors to steps_per_poll whenever the ladder can run — the
    # timing of the latch vs the batch's own stop budgets is racy in a
    # live run, so the boundary rules are asserted directly on a fresh
    # (never-started — no scheduler thread) batcher:
    b = make_batcher(model_and_params, fused_steps_per_dispatch=16)
    try:
        b._pressure.set_budget(100)
        b._pressure.update({"decode": 99})  # latch the high watermark
        assert b._pressure.active
        k, reason = b._fused_plan()
        assert (k, reason) == (b._k, "pressure")
        b._pressure.update({"decode": 0})  # clear
        b._pressure.restore_budget()
        from seldon_core_tpu.serving.continuous import _DrainJob

        b._pending_drain = _DrainJob()
        k, reason = b._fused_plan()
        assert (k, reason) == (b._k, "poll_boundary")
        b._pending_drain = None
        k, reason = b._fused_plan()
        assert (k, reason) == (16, None)  # idle: full K, no shrink
    finally:
        b.close()
    # seeded sampling across preemption, fused on
    b = make_batcher(
        model_and_params, fused_steps_per_dispatch=16,
        hbm_ledger_bytes=1 << 40,
    )
    inj = FaultInjector([], pressure={
        "shrink_to_bytes": shrink,
        "after_polls": b._work_poll_count + 4,
        "restore_after_polls": 12,
    })
    b.pressure_hook = inj.pressure_hook()
    try:
        assert run_batch(b, temperature=0.8) == references["sampled"]
        assert b.stats["preemptions"] >= 1
    finally:
        b.close()


def test_fused_drain_checkpoint_mid_run(model_and_params):
    """Graceful drain mid-fused-run: lanes checkpoint at a poll boundary,
    a peer resumes every checkpoint, and the stitched outputs are
    byte-identical to uninterrupted runs (greedy + seeded)."""
    from seldon_core_tpu.serving.migration import checkpoint_of

    src = make_batcher(model_and_params, fused_steps_per_dispatch=16,
                       steps_per_poll=1)
    peer = make_batcher(model_and_params, fused_steps_per_dispatch=16)
    try:
        futures = [
            src.submit(p, max_new_tokens=40, temperature=t, seed=11)
            for p, t in zip(PROMPTS[:2], (0.0, 0.8))
        ]
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            if any(len(s.emitted) >= 2 for s in src._active.values()):
                break
            time.sleep(0.002)
        drained = src.drain(timeout_s=30.0)
        assert drained, "expected live lanes to drain"
        results = {}
        for req in drained:
            f = peer.submit_checkpoint(
                checkpoint_of(req, src.weight_version)
            )
            results[tuple(req.tokens)] = f.result(timeout=120)
        # reference: uninterrupted step-at-a-time runs
        ref = make_batcher(model_and_params)
        try:
            for p, t in zip(PROMPTS[:2], (0.0, 0.8)):
                exp = ref.generate(p, max_new_tokens=40, temperature=t,
                                   seed=11)
                assert results[tuple(p)] == exp
        finally:
            ref.close()
    finally:
        src.close()
        peer.close()


# -- degradations and accounting ---------------------------------------------


def test_fused_degrades_under_speculation(model_and_params):
    """With a draft configured the fused path stands down: spec bursts
    run (they fuse draft/verify their own way) and the output still
    equals the target's own greedy decode."""
    import jax.numpy as jnp

    model, params = model_and_params
    draft = DecoderLM(
        vocab_size=CFG["vocab_size"], d_model=16, n_layers=1, n_heads=2,
        n_kv_heads=1, d_ff=32, max_seq=64, dtype="float32",
    )
    b = make_batcher(
        model_and_params, fused_steps_per_dispatch=16,
        draft_model=draft, draft_params=draft.init_params(99),
        speculate_tokens=3,
    )
    try:
        p = PROMPTS[0]
        got = b.generate(p, max_new_tokens=10)
        exp = np.asarray(
            model.generate(params, jnp.asarray([p], jnp.int32), 10)
        )[0].tolist()
        assert got == exp
        assert b.stats["spec_rounds"] > 0
        assert b.stats["fused_dispatches"] == 0
    finally:
        b.close()


def test_adaptive_k_shrinks_to_stop_budget(model_and_params):
    """The flight recorder shows K starting at the configured max and
    shrinking (pow2, never below steps_per_poll) as the nearest lane
    approaches its budget."""
    b = make_batcher(model_and_params, slots=2, fused_steps_per_dispatch=16)
    try:
        b.generate(PROMPTS[0], max_new_tokens=20)
        plans = [
            e["plan"] for e in b.flight.dump()["entries"]
            if e.get("type") == "poll" and e.get("plan", {}).get("mode") == "fused"
        ]
        assert plans
        ks = [p["k"] for p in plans]
        assert max(ks) == 16
        assert any(
            p.get("shrunk_by") == "stop_budget" and p["k"] < 16
            for p in plans
        )
        for p in plans:
            assert p["k"] >= b._k  # never below the poll burst
            assert p["k"] & (p["k"] - 1) == 0  # always a warmed pow2
    finally:
        b.close()


def test_steps_per_poll_effective_surfaced(model_and_params):
    """Satellite: the pow2 floor on steps_per_poll is an explicit stat,
    not a silent round-down."""
    b = make_batcher(model_and_params, steps_per_poll=12)
    try:
        assert b.stats["steps_per_poll_effective"] == 8
        assert b._k == 8
    finally:
        b.close()
    b = make_batcher(model_and_params, steps_per_poll=4)
    try:
        assert b.stats["steps_per_poll_effective"] == 4
    finally:
        b.close()


def test_write_pos_parks_writes_out_of_bounds(model_and_params):
    """Model-level freeze primitive: decode_step_ragged_list with
    write_pos >= T leaves the cache bitwise untouched (dropped scatter),
    while the default path writes."""
    import jax.numpy as jnp

    model, params = model_and_params
    B, Tp, T = 2, 5, 16
    rng = np.random.RandomState(0)
    prompt = rng.randint(0, 256, (B, Tp)).astype(np.int32)
    _, cache = model.prefill(params, jnp.asarray(prompt), T)
    ks = [cache["k"][l] for l in range(CFG["n_layers"])]
    vs = [cache["v"][l] for l in range(CFG["n_layers"])]
    tok = jnp.asarray(prompt[:, -1:])
    pos = jnp.full((B,), Tp, jnp.int32)
    park = jnp.full((B,), T, jnp.int32)
    logits_f, nks_f, _ = model.decode_step_ragged_list(
        params, ks, vs, tok, pos, write_pos=park
    )
    logits_w, nks_w, _ = model.decode_step_ragged_list(
        params, ks, vs, tok, pos
    )
    for l in range(CFG["n_layers"]):
        # parked: bitwise unchanged; default: position Tp was written
        np.testing.assert_array_equal(np.asarray(nks_f[l]), np.asarray(ks[l]))
        assert not np.array_equal(np.asarray(nks_w[l]), np.asarray(ks[l]))
    # the forward itself (attention positions, logits) is unaffected by
    # where the write lands THIS step only if the written key is read —
    # the decode step reads its own key, so parked logits legitimately
    # differ; just check shapes/sanity
    assert logits_f.shape == logits_w.shape


def test_generateserver_fused_knob_and_metrics(tmp_path):
    """Knob plumbing + observability: GenerateServer forwards
    fused_steps_per_dispatch, serves identically to a fused-off server,
    and exports gen_fused_steps / gen_fused_dispatches."""
    from seldon_core_tpu.servers.generateserver import GenerateServer

    d = tmp_path / "llm"
    d.mkdir()
    (d / "jax_config.json").write_text(
        json.dumps({"family": "llm", "config": CFG})
    )
    plain = GenerateServer(model_uri=str(d), slots=2, steps_per_poll=2)
    fused = GenerateServer(
        model_uri=str(d), slots=2, steps_per_poll=2,
        fused_steps_per_dispatch=16,
    )
    try:
        body = {"prompt_tokens": [[5, 17, 42], [7, 7, 7, 7]],
                "max_new_tokens": 8}
        seeded = {"prompt_tokens": [[5, 17, 42]], "max_new_tokens": 8,
                  "temperature": 0.8, "seed": 3}
        assert plain.predict(dict(body), [])["tokens"] == \
            fused.predict(dict(body), [])["tokens"]
        assert plain.predict(dict(seeded), [])["tokens"] == \
            fused.predict(dict(seeded), [])["tokens"]
        assert fused.batcher._fused_k == 16
        keys = {m["key"]: m for m in fused.metrics()}
        assert keys["gen_fused_steps"]["type"] == "COUNTER"
        assert keys["gen_fused_steps"]["value"] > 0
        assert keys["gen_fused_dispatches"]["value"] > 0
        # realized K: more fused steps than dispatches
        assert (keys["gen_fused_steps"]["value"]
                > keys["gen_fused_dispatches"]["value"])
        assert "gen_fused_steps" not in {
            m["key"] for m in plain.metrics()
        }
    finally:
        if plain.batcher:
            plain.batcher.close()
        if fused.batcher:
            fused.batcher.close()


def test_flight_report_k_collapse_diagnosis():
    """The K-collapse DIAGNOSIS fires when realized K pins at its shrink
    floor (which is min(steps_per_poll, k_max), never 1 for
    steps_per_poll > 1), and stays quiet on a healthy run."""
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "flight_report",
        os.path.join(os.path.dirname(__file__), "..", "tools",
                     "flight_report.py"),
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)

    def dump(ks):
        return {
            "entries": [
                {"type": "poll", "active": 2, "queue": 0, "admitted": 0,
                 "plan": {"mode": "fused", "k": k, "k_max": 64,
                          "shrunk_by": "pressure", "lanes": 2,
                          "bucket": 128, "distinct_buckets": 1}}
                for k in ks
            ],
            "recorded_total": len(ks), "dropped": 0,
        }

    # ledger latched for the whole run: every poll at the floor (8), far
    # below the configured 64 — the old `k <= 1` check missed this
    collapsed = "\n".join(mod.diagnose(dump([8] * 6)))
    assert "DIAGNOSIS: K collapsed to 8 (configured 64)" in collapsed
    # healthy: every poll at k_max
    healthy = "\n".join(mod.diagnose(dump([64] * 6)))
    assert "DIAGNOSIS: K collapsed" not in healthy
    # mixed but mostly healthy: below the half-of-polls threshold
    mixed = "\n".join(mod.diagnose(dump([64] * 10 + [8] * 2)))
    assert "DIAGNOSIS: K collapsed" not in mixed


# -- the burst executable against the step-at-a-time model --------------------
# ISSUE 26: one way to write and read the cache (DecoderLM._cache_write /
# _cache_read). The burst executables over the UNSTACKED per-layer cache
# must give the greedy tokens and the final cache of eight
# decode_step_ragged calls over the STACKED cache.

BURST_T = 384  # "full" must lie beyond the 256 bucket
BURST_K = 8


def _burst_case(attn_len):
    """Four ragged lanes: one whose last write lands at ``attn_len - 1``,
    one inactive, two mid-cache."""
    top = attn_len if attn_len is not None else BURST_T
    pos = np.array([top - BURST_K, 37, 5, top // 2], np.int32)
    active = np.array([True, False, True, True])
    cur = np.array([11, 22, 33, 44], np.int32)
    return pos, active, cur


def _burst_state(model, seed=3):
    """A cache full of seeded noise, stacked and as per-layer lists: a
    read that strays past a lane's bound, or a write that lands in the
    wrong place, changes a number."""
    import jax
    import jax.numpy as jnp

    stacked = model.init_cache(4, BURST_T)
    key = jax.random.PRNGKey(seed)
    stacked = {
        name: jax.random.normal(k, a.shape, a.dtype) * 0.3
        for (name, a), k in zip(sorted(stacked.items()),
                                jax.random.split(key, 2))
    }
    n = stacked["k"].shape[0]
    lists = {name: [jnp.array(stacked[name][l]) for l in range(n)]
             for name in ("k", "v")}
    return stacked, lists


def _reference_burst(model, params, stacked, cur, pos, active, attn_len,
                     budgets=None):
    """BURST_K greedy decode_step_ragged calls. With ``budgets`` (the
    stop-aware burst) a lane freezes (token, position and cache) once
    its budget is spent, which is what parking its writes out of bounds
    must amount to; without, an inactive lane's token reads 0 and its
    cache is written where it stands, as fused_step does."""
    import jax
    import jax.numpy as jnp

    step = jax.jit(model.decode_step_ragged, static_argnums=(4,))
    cur, pos, active = jnp.asarray(cur), jnp.asarray(pos), jnp.asarray(active)
    rows = [np.asarray(cur)]
    freezes = budgets is not None
    left = jnp.asarray(budgets) if freezes else jnp.full_like(pos, BURST_K)
    for _ in range(BURST_K):
        alive = active & (left > 0)
        logits, new = step(params, stacked, cur[:, None], pos, attn_len)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        if freezes:
            keep = alive[None, :, None, None, None]
            new = {n: jnp.where(keep, new[n], stacked[n]) for n in new}
        stacked = new
        cur = jnp.where(alive, nxt, cur if freezes else 0)
        rows.append(np.asarray(jnp.where(alive, cur, 0)))
        left = left - alive.astype(jnp.int32)
        pos = jnp.where(alive, pos + 1, pos)
    return np.stack(rows), np.asarray(pos), stacked


def _run_burst(b, kind, params, lists, cur, pos, active, budgets, attn_len):
    """``_burst_fn`` or (``stop_burst``) ``_fused_burst_fn`` over the
    donated per-layer lists, greedy: ``(toks, pos, cache, counts, done)``."""
    import jax
    import jax.numpy as jnp

    args = (params, lists, jnp.asarray(cur), jnp.asarray(pos),
            jnp.asarray(active), jnp.zeros((4,), jnp.float32),
            jax.vmap(jax.random.PRNGKey)(jnp.arange(4)))
    if kind == "burst":
        toks, _, pos, cache, _ = b._burst_fn(*args, BURST_K, attn_len)
        return toks, pos, cache, None, None
    toks, counts, done, _, pos, cache, _, _ = b._fused_burst_fn(
        *args, jnp.full((4,), -1, jnp.int32), jnp.asarray(budgets),
        BURST_K, attn_len)
    return toks, pos, cache, counts, done


@pytest.mark.parametrize("attn_len", [128, 256, None])
@pytest.mark.parametrize("kind", ["burst", "stop_burst"])
def test_burst_executable_matches_stepwise_model(model_and_params, kind,
                                                 attn_len):
    model, params = model_and_params
    b = make_batcher(model_and_params, slots=1, max_seq=16)
    try:
        pos0, active, cur0 = _burst_case(attn_len)
        stacked, lists = _burst_state(model)
        # a lane of its own runs out of budget after 3 steps: its writes
        # park out of bounds and must be dropped, not clamped onto T - 1
        budgets = np.array([BURST_K, BURST_K, 3, BURST_K], np.int32)
        toks, pos, cache, counts, done = _run_burst(
            b, kind, params, lists, cur0, pos0, active, budgets, attn_len)
        # the stepwise model on the tree the burst is handed (its q / k / v
        # weights contraction-minor: in float32 the CPU's matmul sums in
        # another order by layout, and the rows are compared bit for bit)
        assert b.params is params
        want_toks, want_pos, want = _reference_burst(
            model, model.burst_params(params), stacked, cur0, pos0, active,
            attn_len, budgets=budgets if kind == "stop_burst" else None)
        if kind == "stop_burst":
            np.testing.assert_array_equal(np.asarray(counts), [8, 0, 3, 8])
            assert np.asarray(done).all()
        np.testing.assert_array_equal(np.asarray(toks), want_toks)
        np.testing.assert_array_equal(np.asarray(pos), want_pos)
        for name in ("k", "v"):
            for l, layer in enumerate(cache[name]):
                np.testing.assert_array_equal(
                    np.asarray(layer), np.asarray(want[name][l]),
                    err_msg=f"{name}[{l}]")
        # the lane at the bucket's edge did write its last position
        top = (attn_len or BURST_T) - 1
        assert not np.array_equal(
            np.asarray(cache["k"][0][0, :, top]),
            np.asarray(stacked["k"][0, 0, :, top]))
    finally:
        b.close()


@pytest.mark.parametrize("kind", ["burst", "stop_burst"])
def test_burst_consumes_donated_cache(model_and_params, kind):
    """The cache leaves are donated: the burst consumes them and, where
    the platform says where a buffer lives, hands the same buffers back."""
    import jax

    model, params = model_and_params
    b = make_batcher(model_and_params, slots=1, max_seq=16)
    try:
        pos0, active, cur0 = _burst_case(128)
        _, lists = _burst_state(model)
        leaves = lists["k"] + lists["v"]
        jax.block_until_ready(leaves)
        try:
            before = [a.unsafe_buffer_pointer() for a in leaves]
        except Exception:  # a platform that does not say
            before = None
        cache = _run_burst(b, kind, params, lists, cur0, pos0, active,
                           np.full((4,), BURST_K, np.int32), 128)[2]
        jax.block_until_ready(cache)
        assert all(a.is_deleted() for a in leaves)
        if before is not None:
            after = [a.unsafe_buffer_pointer()
                     for a in cache["k"] + cache["v"]]
            assert sorted(after) == sorted(before)
    finally:
        b.close()
