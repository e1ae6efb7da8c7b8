"""``models/evabyte.py`` on the CPU at tiny widths (window 32, chunk 4): the
family's prefill in all its forms and its decode step, through the
batcher's own ``insert_many`` and burst, against the plain reference
(``benchmark/reference/evabyte.py``) in float32; the chunk-edge and
window-edge crossings, and the cache the steps leave against one prefill
of the same bytes; what the family tells the scheduler of its cache, with
the other families' answers unchanged; the summaries the kernel writes,
counted by the batcher; every typed refusal.

    JAX_PLATFORMS=cpu python -m pytest tests/test_evabyte.py -q
"""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.reference import evabyte as reference
from seldon_core_tpu.models.llm import DecoderLM, UnsupportedByModel
from seldon_core_tpu.serving.continuous import ContinuousBatcher

W, C, P, V = 32, 4, 3, 64
KW = dict(block="evabyte", vocab_size=V, d_model=64, n_layers=2, n_heads=4,
          n_kv_heads=4, d_ff=128, max_seq=128, rope_theta=1e5, dtype="float32",
          window_size=W, chunk_size=C, num_pred_heads=P,
          norm_add_unit_offset=True, fp32_skip_add=True, residual_scale=0.5)
TOL = 2e-4      # float32, as tests/test_qwen3_next.py


@pytest.fixture(scope="module")
def model():
    return DecoderLM(**KW)


@pytest.fixture(scope="module")
def params(model):
    return model.init_params(0)


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(0).integers(0, V, size=128)


@pytest.fixture(scope="module")
def ref(model, params, tokens):
    """The reference's ONE forward over all 128 tokens: logits of every
    head at every position, K, V at every position and every summary."""
    return reference.forward(params, model.cfg, tokens, list(range(128)),
                             rows_at=np.arange(128))


def _prefill(model, params, tokens, n):
    bucket = W if n <= W else 128
    prompt = np.zeros((1, bucket), np.int32)
    prompt[0, :n] = tokens[:n]
    return bucket, model._prefill(params, jnp.asarray(prompt), bucket,
                                  jnp.asarray([n - 1], jnp.int32))


def test_the_family_is_built_by_its_block(model):
    from seldon_core_tpu.models.evabyte import EvaByteLM

    assert type(model) is EvaByteLM
    assert model.n_params() == sum(
        a.size for a in jax.tree_util.tree_leaves(model.init_params(1)))
    for bad in (dict(KW, chunk_size=5), dict(KW, window_size=48),
                dict(KW, n_kv_heads=2), dict(KW, num_pred_heads=0)):
        with pytest.raises(ValueError):
            DecoderLM(**bad)


# shorter than a chunk; on a chunk's edge and one short of it; on a
# window's edge and one past it; spanning three windows and more
@pytest.mark.parametrize("n", [3, 7, 8, 31, 32, 33, 64, 65, 90, 97, 128])
def test_a_prefill_is_the_references_forward_at_every_window_count(
        model, params, tokens, ref, n):
    bucket, (logits, slab) = _prefill(model, params, tokens, n)
    logits_ref, ref_k, ref_v, ref_sk, ref_sv = ref
    # all heads, at the prompt's last position
    np.testing.assert_allclose(logits[0], logits_ref[n - 1], atol=TOL)
    # the slab: the LAST window's ring rows and every whole chunk's summary
    w0 = (n - 1) // W * W
    for l in range(2):
        for kind, theirs in (("window_k", ref_k), ("window_v", ref_v)):
            np.testing.assert_allclose(
                slab[kind][l, 0, :, :n - w0],
                theirs[l][w0:n].transpose(1, 0, 2), atol=TOL)
        for kind, theirs in (("summary_k", ref_sk), ("summary_v", ref_sv)):
            np.testing.assert_allclose(
                slab[kind][l, 0, :, :n // C],
                theirs[l][:n // C].transpose(1, 0, 2), atol=TOL)
    # the walk's work: the prompt's own windows, not the bucket's
    counts = np.asarray(model.windows_walked(bucket, jnp.asarray([n - 1])))
    assert counts.tolist() == [-(-n // W), max(1, bucket // W)]
    assert slab["window_k"].shape == (2, 1, 4, min(bucket, W), 16)
    assert slab["summary_k"].shape == (2, 1, 4, bucket // C, 16)


def test_a_batched_walk_gives_each_row_its_own_last_window(
        model, params, tokens, ref):
    lens = [40, 97, 64]
    prompt = np.zeros((3, 128), np.int32)
    for i, n in enumerate(lens):
        prompt[i, :n] = tokens[:n]
    logits, slab, counts = model.prefill_counted(
        params, jnp.asarray(prompt), 128, jnp.asarray([n - 1 for n in lens]))
    assert np.asarray(counts).tolist() == [2 + 4 + 2, 3 * 4]
    for i, n in enumerate(lens):
        np.testing.assert_allclose(logits[i], ref[0][n - 1, 0], atol=TOL)
        w0 = (n - 1) // W * W
        np.testing.assert_allclose(
            slab["window_k"][0, i, :, :n - w0],
            ref[1][0][w0:n].transpose(1, 0, 2), atol=TOL)
    with pytest.raises(ValueError, match="prefill_lengths"):
        model._forward(params, jnp.zeros((1, 48), jnp.int32), jnp.asarray([3]))
    assert model.apply(params, jnp.asarray(tokens[None, :W])).shape == (1, W, V)


@pytest.fixture
def batcher(model, params):
    b = ContinuousBatcher(model, params, slots=6, max_seq=128)
    yield b
    b.close()


def _admit(batcher, params, tokens, lanes):
    """``{lane: length}`` through the batcher's own batched prefill and
    ``insert_many``, a group a bucket."""
    cache = batcher._cache
    batcher._cache = None
    regs = (jnp.zeros((batcher.slots,), jnp.int32),
            jnp.zeros((batcher.slots,), jnp.int32),
            jnp.zeros((batcher.slots, 2), jnp.uint32))
    counted = list(batcher._no_prefill_counts)
    by_bucket = {}
    for lane, n in lanes.items():
        by_bucket.setdefault(batcher._bucket(n), []).append((lane, n))
    for bucket, group in by_bucket.items():
        # a walked bucket takes one prompt a call (``prefill_rows_max``)
        for rows in ([[row] for row in group] if bucket > W else [group]):
            prompt = np.zeros((len(rows), bucket), np.int32)
            for i, (_lane, m) in enumerate(rows):
                prompt[i, :m] = tokens[:m]
            last = jnp.asarray([m - 1 for _l, m in rows], jnp.int32)
            firsts, slab, keys, *counts = batcher._prefill_many_fn(
                params, jnp.asarray(prompt), last,
                jnp.zeros((len(rows),), jnp.int32),
                jnp.zeros((len(rows),), jnp.float32))
            cache, *regs, counted = batcher._insert_many_fn(
                cache, slab, jnp.asarray([l for l, _m in rows], jnp.int32),
                jnp.asarray([tokens[m] for _l, m in rows], jnp.int32),
                last + 1, keys, *regs, *counted, *counts)
            counted = [counted]
    return cache, regs, np.asarray(counted[0])


# lanes whose next steps cross a chunk's edge (6 -> 7 | 8), a window's
# edge (30, 31 | 32: the summary written at 31 is read at 32), both at
# once three windows in (94), and two that cross neither
LANES = {0: 6, 1: 30, 3: 94, 4: 45, 5: 17}


def test_insert_many_and_the_burst_follow_the_reference_over_the_edges(
        model, params, tokens, ref, batcher):
    assert batcher.prefill_buckets == (32, 128) and batcher._ragged_read
    cache, (cur_tok, pos, keys), counted = _admit(batcher, params, tokens, LANES)
    assert counted.tolist() == [1 + 1 + 3 + 2 + 1, 1 + 1 + 4 + 4 + 1]
    live = np.array([lane in LANES for lane in range(6)])
    at = np.array([LANES.get(lane, 0) for lane in range(6)])
    np.testing.assert_array_equal(np.asarray(pos), at)
    k = 5
    # the burst samples head 0's argmax; fed the prompt's own tokens
    # instead, step by step, every head's logits are the reference's
    step = jax.jit(model._step)
    mine = cache
    for i in range(k):
        t = np.where(live, at + i, 0)
        logits, mine, counts = step(
            params, mine, jnp.asarray(tokens[t][:, None], jnp.int32),
            jnp.asarray(t, jnp.int32), None, None,
            jnp.asarray(np.where(live, t + 1, 0), jnp.int32))
        for lane in LANES:
            np.testing.assert_allclose(logits[lane], ref[0][t[lane]], atol=TOL)
        tl = t[live]
        assert np.asarray(counts).tolist() == [2 * int(v) for v in (
            (tl % W + 1).sum(), (tl // W * (W // C)).sum(),
            (-(-(tl % W + 1) // 128) * 128 + -(-(tl // W * (W // C)) // 128)
             * 128).sum(), (tl + 1).sum(), ((tl + 1) % C == 0).sum(),
            live.sum())]
    # the rows the steps wrote, both kinds, are the reference's
    for lane, n in LANES.items():
        for i in range(k):
            np.testing.assert_allclose(
                mine["window_k"][1][lane, :, (n + i) % W],
                ref[1][1][n + i], atol=TOL)
        done = (n + k) // C
        np.testing.assert_allclose(
            mine["summary_v"][0][lane, :, :done],
            ref[4][0][:done].transpose(1, 0, 2), atol=TOL)
    # the batcher's own burst from the same registers: its tokens are head
    # 0's argmax at each step, fed back
    toks, _cur, new_pos, burst_cache, _keys, burst_counts = batcher._burst_fn(
        params, cache, cur_tok, pos, jnp.asarray(live),
        jnp.zeros((6,), jnp.float32), keys, k, None)
    batcher._cache = burst_cache
    toks = np.asarray(toks)
    np.testing.assert_array_equal(np.asarray(new_pos)[live], at[live] + k)
    np.testing.assert_array_equal(toks[0][live], tokens[at[live]])
    first = ref[0][at[live], 0].argmax(-1)      # the reference's next byte
    np.testing.assert_array_equal(toks[1][live], first)
    assert np.asarray(burst_counts)[5] == 2 * k * live.sum()
    assert np.asarray(burst_counts)[4] == 2 * sum(
        (n + i + 1) % C == 0 for n in LANES.values() for i in range(k))
    # the idle lane (2) was written in neither kind
    for kind in burst_cache:
        assert not np.asarray(burst_cache[kind][0][2]).any()


# from inside a chunk and a window (5, 17: 40 steps cross ten chunk edges
# and one window's, two for the second), and from a chunk's and a window's
# own last position (31, 63: the first step lands the summary the second
# one reads; 63 + 40 crosses into the fourth window)
@pytest.mark.parametrize("starts", [{0: 5, 2: 17}, {1: 31, 4: 63}],
                         ids=["from_inside_a_chunk", "from_a_windows_edge"])
def test_decode_steps_leave_the_cache_one_prefill_of_the_same_bytes_would(
        model, params, tokens, batcher, starts):
    """Forty steps fed the prompt's own bytes, every summary row pooled
    and landed by the step's own ``eva_decode_attention`` call, against ONE
    prefill of the bytes the lane then holds: the ring's rows of the last
    window and every whole chunk's summary, both kinds of each."""
    cache, _regs, _ = _admit(batcher, params, tokens, starts)
    batcher._cache = cache
    live = np.array([lane in starts for lane in range(6)])
    at = np.array([starts.get(lane, 0) for lane in range(6)])
    step = jax.jit(model._step)
    k = 40
    for i in range(k):
        t = np.where(live, at + i, 0)
        _logits, cache, counts = step(
            params, cache, jnp.asarray(tokens[t][:, None], jnp.int32),
            jnp.asarray(t, jnp.int32), None, None,
            jnp.asarray(np.where(live, t + 1, 0), jnp.int32))
        assert int(counts[4]) == 2 * int(((t[live] + 1) % C == 0).sum())
    for lane, n0 in starts.items():
        n = n0 + k
        _bucket, (_l, slab) = _prefill(model, params, tokens, n)
        w0 = (n - 1) // W * W
        for l in range(2):
            for kind in ("window_k", "window_v"):
                np.testing.assert_allclose(
                    cache[kind][l][lane, :, :n - w0],
                    slab[kind][l, 0, :, :n - w0], atol=TOL)
            for kind in ("summary_k", "summary_v"):
                np.testing.assert_allclose(
                    cache[kind][l][lane, :, :n // C],
                    slab[kind][l, 0, :, :n // C], atol=TOL)
                # and no row past the lane's last whole chunk was written
                assert not np.asarray(cache[kind][l][lane, :, n // C:]).any()
    # the idle lanes were written in neither kind
    for kind in cache:
        for lane in np.flatnonzero(~live):
            assert not np.asarray(cache[kind][0][lane]).any()


def test_a_lane_admitted_beside_live_lanes_leaves_them_bit_equal(
        model, params, tokens, batcher):
    cache, regs, _ = _admit(batcher, params, tokens, {0: 30, 3: 94})
    before = jax.tree_util.tree_map(np.asarray, cache)
    # the newcomer walks three windows into lane 1
    prompt = np.zeros((1, 128), np.int32)
    prompt[0, :70] = tokens[:70]
    first, slab, key, *counts = batcher._prefill_fn(
        params, jnp.asarray(prompt), jnp.asarray([69], jnp.int32),
        jnp.int32(0), jnp.float32(0.0))
    cache, cur_tok, pos, keys, _c = batcher._insert_fn(
        cache, slab, 1, first[0], 70, key, *regs,
        *batcher._no_prefill_counts, *counts)
    batcher._cache = cache
    assert np.asarray(pos).tolist() == [30, 70, 0, 94, 0, 0]
    for kind in cache:
        for l in range(2):
            after = np.asarray(cache[kind][l])
            for lane in (0, 2, 3, 4, 5):
                np.testing.assert_array_equal(after[lane], before[kind][l][lane])
            assert after[1].any()


def test_a_parked_lane_writes_in_neither_kind(model, params, tokens, batcher):
    """The stop-aware burst parks a finished lane's write at the family's
    ``park_index``: the ring wraps, so a park inside it would alias a live
    row (``park mod 32`` is row 0)."""
    cache, (cur_tok, pos, keys), _ = _admit(batcher, params, tokens,
                                           {0: 31, 1: 63})
    park = model.park_index(cache)
    assert park == 128 and park % W == 0
    before = jax.tree_util.tree_map(np.asarray, cache)
    t = jnp.asarray([31, 63, 0, 0, 0, 0], jnp.int32)
    feed = jnp.asarray(tokens[np.asarray(t)][:, None], jnp.int32)
    # lane 1 is done: parked and unread; lane 0 completes a chunk AND its
    # window at t = 31
    _logits, after, counts = model.decode_step_cache(
        params, cache, feed, t, write_pos=jnp.asarray(
            [31, park, park, park, park, park], jnp.int32),
        lens=jnp.asarray([32, 0, 0, 0, 0, 0], jnp.int32))
    assert np.asarray(counts).tolist() == [2 * 32, 0, 2 * 128, 2 * 32, 2, 2]
    for kind in after:
        for l in range(2):
            got = np.asarray(after[kind][l])
            for lane in range(1, 6):
                np.testing.assert_array_equal(got[lane], before[kind][l][lane])
    assert not np.array_equal(np.asarray(after["window_k"][0][0, :, 31]),
                              before["window_k"][0][0, :, 31])
    assert np.asarray(after["summary_k"][0][0, :, 7]).any()
    # and through the batcher's stop-aware burst: a lane out of budget
    # freezes while the other goes on
    batcher._cache = after
    toks, n_emitted, done, *_rest = batcher._fused_burst_fn(
        params, after, cur_tok, pos, jnp.asarray([True, True] + [False] * 4),
        jnp.zeros((6,), jnp.float32), keys, jnp.full((6,), -1, jnp.int32),
        jnp.asarray([4, 1, 0, 0, 0, 0], jnp.int32), 4, None)
    batcher._cache = _rest[2]
    assert np.asarray(n_emitted).tolist()[:2] == [4, 1]
    assert np.asarray(done).tolist()[:2] == [True, True]
    frozen = np.asarray(_rest[2]["window_k"][0][1])
    # lane 1 wrote its one step's row (position 63) and nothing after it:
    # its ring's row 0 is still its prompt's position 32
    np.testing.assert_array_equal(frozen[:, 0], before["window_k"][0][1][:, 0])


def test_what_the_family_tells_the_scheduler_of_its_cache(model, batcher):
    cache = batcher._cache
    row = 2 * 4 * 16 * 4                     # K and V, 4 heads of 16, float32
    assert model.cache_position_bytes(cache) == 2 * row     # 2 layers
    assert len(model.position_layers(cache)) == 4
    price = model.lane_cache_bytes(cache)
    # inside its window a position costs a ring row a layer; after it, a
    # summary row a chunk of 4
    assert [price(n) for n in (0, 1, 32, 33, 64, 128)] == [
        0, 2 * row, 32 * 2 * row, (1 + 8) * 2 * row, (32 + 8) * 2 * row,
        (32 + 24) * 2 * row]
    assert batcher._lane_bytes(33) == (1 + 8) * 2 * row
    ring, summ = model.rows_at(np.array([1, 32, 33, 100]))
    assert ring.tolist() == [1, 32, 1, 4] and summ.tolist() == [0, 0, 8, 24]
    assert model.prefill_lengths((16, 32, 40, 48, 64, 128, 130), 128) == (
        16, 32, 64, 128)
    assert batcher.prefill_buckets == (32, 128)
    assert [model.prefill_rows_max(b) for b in (16, 32, 64, 128)] == [8, 8, 1, 1]
    assert model.admissions_per_turn() == 1 == batcher._admit_cap
    assert batcher._rows_ok(4, 32) and batcher._rows_ok(1, 128)
    assert not batcher._rows_ok(4, 128) and not batcher._chunk8_ok(128)
    # a slab is the last window's ring and a row a chunk: bfloat16's bytes
    assert model.prefill_slab_bytes(1, 128) == 2 * (32 + 32) * (2 * 4 * 16 * 2)
    assert model.prefill_slab_bytes(4, 16) == 2 * 4 * (16 + 4) * (2 * 4 * 16 * 2)
    assert model.burst_reads_ragged(cache) and model.attention_kinds() == ()
    ring, summ = model.rows_at(100)
    assert model.dispatch_read_bytes("decode_burst", rows=6, live=2, k=3,
                                     bucket=100) == 3 * (
        model.step_param_bytes() + 2 * (ring + summ) * model.kv_bytes_per_token())
    assert model.flops_per_token(100) > model.flops_per_token(33)
    assert model.decode_bytes_per_token(100) > model.step_param_bytes()


FAMILIES = {
    "llama": dict(vocab_size=64, d_model=64, n_layers=2, n_heads=4,
                  n_kv_heads=2, d_ff=128, max_seq=128),
    "afmoe": dict(block="afmoe", vocab_size=64, d_model=64, n_layers=2,
                  n_heads=4, n_kv_heads=2, d_ff=128, max_seq=128,
                  layer_types=("sliding_attention", "full_attention"),
                  sliding_window=32, n_dense_layers=1, n_routed_experts=4,
                  experts_per_tok=2, expert_width=32, n_shared_experts=1),
    "qwen3_next": dict(block="qwen3_next", vocab_size=64, d_model=64,
                       n_layers=2, n_heads=4, n_kv_heads=2, head_dim=32,
                       d_ff=128, max_seq=128,
                       layer_types=("linear_attention", "full_attention"),
                       linear_key_heads=2, linear_value_heads=4,
                       linear_key_dim=16, linear_value_dim=16,
                       linear_conv_kernel=4, partial_rotary_factor=0.25,
                       n_routed_experts=4, experts_per_tok=2, expert_width=32,
                       shared_expert_width=32),
    "joyai_llm_flash": dict(block="joyai_llm_flash", vocab_size=64, d_model=64,
                            n_layers=2, n_heads=4, n_kv_heads=4, d_ff=128,
                            max_seq=128, q_lora_rank=32, kv_lora_rank=128,
                            qk_nope_head_dim=16, qk_rope_head_dim=16,
                            v_head_dim=16, n_dense_layers=1,
                            n_routed_experts=4, experts_per_tok=2,
                            expert_width=32, n_shared_experts=1),
}


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_the_accepted_families_answers_are_what_they_were(family):
    """One row a position, positions from 0, one length for every kind: the
    drop index is the positions' axis's end, a lane's bytes are its
    positions times ``cache_position_bytes``, every bucket is taken and a
    batched prefill in one takes eight rows, as before the scheduler asked
    (past the buckets every 512 below ``max_seq``, the default rule since
    PR 52: ``tests/test_prefill_lengths.py``)."""
    m = DecoderLM(**FAMILIES[family])
    cache = m.cache_layers(3, 128)
    assert m.park_index(cache) == 128 == m.position_layers(cache)[0].shape[-2]
    per = m.cache_position_bytes(cache)
    assert per > 0
    price = m.lane_cache_bytes(cache)
    assert [price(n) for n in (0, 1, 100, 128)] == [0, per, 100 * per, 128 * per]
    buckets = (32, 128, 512, 1024, 1792)
    assert m.prefill_lengths(buckets, 2048) == buckets
    assert m.prefill_lengths(buckets, 4096) == (
        *buckets, 2048, 2560, 3072, 3584)
    assert [m.prefill_rows_max(b) for b in buckets] == [8] * 5
    assert m.admissions_per_turn() == 0


def _admitted_a_poll(b):
    return [e["admitted"] for e in b.flight.snapshot()
            if e["type"] == "poll" and e.get("admitted")]


def _three_wait_before_the_loop_starts(b, tokens):
    """Three prompts queued while ``submit`` is kept from starting the
    loop, so that its first turn finds all three and three free lanes."""
    start, b.start = b.start, lambda: None
    futures = [b.submit(tokens[:n].tolist(), max_new_tokens=6)
               for n in (70, 40, 20)]
    b.start = start
    b.start()
    return [list(f.result(timeout=300)) for f in futures]


def test_a_turn_admits_one_prompt_and_a_burst_runs_between_two(
        model, params, tokens):
    """Each turn takes one of three waiting prompts
    (``admissions_per_turn``), so the first is decoding while the second
    walks its windows, and all three read what they read alone. A dense
    family's loop takes every free lane in its first turn."""
    alone = ContinuousBatcher(model, params, slots=3, max_seq=128)
    try:
        want = [list(alone.submit(tokens[:n].tolist(), max_new_tokens=6)
                     .result(timeout=300)) for n in (70, 40, 20)]
    finally:
        alone.close()
    b = ContinuousBatcher(model, params, slots=3, max_seq=128)
    try:
        assert _three_wait_before_the_loop_starts(b, tokens) == want
        assert _admitted_a_poll(b) == [1, 1, 1]
    finally:
        b.close()
    dense = DecoderLM(**FAMILIES["llama"])
    d = ContinuousBatcher(dense, dense.init_params(0), slots=3, max_seq=128)
    try:
        assert d._admit_cap == 3
        got = _three_wait_before_the_loop_starts(d, tokens)
        assert [len(g) for g in got] == [76, 46, 26]
        assert _admitted_a_poll(d) == [3]
    finally:
        d.close()


def test_the_ledger_prices_a_dense_lane_as_before():
    m = DecoderLM(**FAMILIES["llama"])
    b = ContinuousBatcher(m, m.init_params(0), slots=2, max_seq=128)
    try:
        per = b._kv_key_bytes
        assert b._lane_bytes(96) == 96 * per
        assert b.prefill_buckets == (32, 128)
        assert b._rows_ok(4, 128) and b._rows_ok(8, 32) and b._chunk8_ok(128)

        class Req:
            tokens = [1] * 40
            max_new_tokens = 30

        assert b._admit_cost_bytes(Req()) == b._attn_need(70) * per
    finally:
        b.close()


@pytest.mark.parametrize("feature", [
    "speculation", "mesh", "kv_tier", "prefix_cache", "chunked_prefill",
    "preemption", "migration"])
def test_every_refusal_is_typed_and_says_why(model, feature):
    assert set(model.serving_refuses) == {
        "speculation", "mesh", "kv_tier", "prefix_cache", "chunked_prefill",
        "preemption", "migration"}
    with pytest.raises(UnsupportedByModel, match=feature):
        model.check_serves(**{feature: True})
    assert len(model.serving_refuses[feature]) > 40
    model.check_serves(**{feature: False})


def test_the_paths_it_has_not_are_refused_typed(model, params):
    for call in (model.backbone, model.loss_fn, model._decode,
                 model.decode_step_ragged_list, model.decode_chunk_ragged_list,
                 model.prefill_chunk, model.prefill_with_prefix):
        with pytest.raises(UnsupportedByModel, match="evabyte block"):
            call()
    with pytest.raises(UnsupportedByModel, match="serving mesh"):
        model.param_sharding(None, params)
    with pytest.raises(UnsupportedByModel):
        ContinuousBatcher(model, params, slots=2, max_seq=128, prefill_chunk=32)


IN_KERNEL = "eva_summaries_written_in_kernel"


@pytest.mark.parametrize("lowered", [False, True],
                         ids=["off_a_tpu", "where_the_kernel_writes"])
def test_the_batcher_mirrors_the_summaries_the_kernel_writes(
        model, params, tokens, monkeypatch, lowered):
    """``stats["eva_summaries_written_in_kernel"]``: 0 where the model's
    step is lowered to the scatters (here, off a TPU), the step counter
    ``eva_summaries_written`` itself where the model says the kernel lands
    the rows (a stub: no TPU here)."""
    if lowered:
        monkeypatch.setattr(
            model, "step_counters_in_kernel",
            lambda cache, mesh=None: {IN_KERNEL: "eva_summaries_written"})
    else:
        cache = model.init_cache(2, 128)
        assert model.step_counters_in_kernel(cache) == {IN_KERNEL: None}
    b = ContinuousBatcher(model, params, slots=2, max_seq=128)
    try:
        assert b.stats[IN_KERNEL] == 0
        b.start()
        list(b.submit(tokens[:30].tolist(), max_new_tokens=11).result(timeout=300))
        written = b.stats["eva_summaries_written"]
        # positions 30 .. 39 and on to the burst's end are stepped: chunks
        # end at 31, 35, 39, each in two layers
        assert written >= 2 * 3 and written % 2 == 0
        assert b.stats[IN_KERNEL] == (written if lowered else 0)
    finally:
        b.close()


def test_a_family_that_names_no_kernel_write_gets_no_key():
    m = DecoderLM(**FAMILIES["afmoe"])
    # the interface's default: no counter of this family is a kernel's write
    assert m.step_counters_in_kernel(m.cache_layers(2, 128)) == {}
    b = ContinuousBatcher(m, m.init_params(0), slots=2, max_seq=128)
    try:
        assert b._counters_in_kernel == {}
        assert set(m.step_counter_names) <= set(b.stats)
        assert not [name for name in b.stats
                    if name.endswith("_in_kernel")
                    and name != "kv_rows_written_in_kernel"]
    finally:
        b.close()


def test_it_serves_bytes_through_the_batcher(model, params, tokens, ref):
    """What it does serve: greedy requests through ``submit``, a long
    prompt walking its own windows, the same bytes twice, and the first of
    them the reference's next byte."""
    b = ContinuousBatcher(model, params, slots=3, max_seq=128)
    try:
        b.start()
        for n in (5, 32, 70):
            prompt = tokens[:n].tolist()
            out = list(b.submit(prompt, max_new_tokens=9).result(timeout=300))
            again = list(b.submit(prompt, max_new_tokens=9).result(timeout=300))
            assert out == again and len(out) == n + 9
            assert out[n] == int(ref[0][n - 1, 0].argmax())
            grown = reference.forward(params, model.cfg, np.asarray(out),
                                      list(range(n - 1, n + 8)))[0]
            assert out[n:] == grown[:, 0].argmax(-1).tolist()
        # 5 -> 1 of 1, 32 -> 1 of 1, 70 -> 3 of 4 windows, each twice
        assert b.stats["eva_prefill_windows_walked"] == 2 * (1 + 1 + 3)
        assert b.stats["eva_prefill_windows_bucket"] == 2 * (1 + 1 + 4)
        assert b.stats["eva_lane_steps"] > 0
        assert b.stats["eva_summaries_written"] > 0
    finally:
        b.close()


# -- the burst's params (ISSUE 54): every layer's q / k / v weights and the
# head, held contraction-minor once beside the stored ones -------------------


def test_burst_params_hold_the_projections_and_the_head_contraction_minor(
        model, params):
    bp = model.burst_params(params)
    assert "unembed" not in bp and bp["unembed_t"].shape == (P * V, 64)
    assert bp["embed"] is params["embed"] and bp["ln_f"] is params["ln_f"]
    for p, q in zip(params["layers"], bp["layers"]):
        assert sorted(q) == sorted(
            {"wq_t", "wk_t", "wv_t"} | (set(p) - {"wq", "wk", "wv"}))
        for w in ("wq", "wk", "wv"):
            np.testing.assert_array_equal(q[w + "_t"], np.asarray(p[w]).T)
        assert all(q[k] is p[k] for k in p if k not in ("wq", "wk", "wv"))
    assert "wq" in params["layers"][0] and "unembed" in params


def test_the_step_on_burst_params_is_the_step_on_params_bit_for_bit(tokens):
    """In the serving dtype: every head's logits, both kinds of rows and the
    counters, at a step that completes a chunk and its window (31), one
    inside a chunk and an idle lane."""
    m = DecoderLM(**dict(KW, dtype="bfloat16"))
    p = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16),
                               m.init_params(0))
    prompt = np.zeros((1, 32), np.int32)
    prompt[0] = tokens[:32]
    _logits, slab = m.prefill(p, jnp.asarray(prompt), 32,
                              jnp.asarray([30], jnp.int32))
    cache = m.cache_layers(3, 128)
    for kind, layers in cache.items():
        for l in range(2):
            layers[l] = layers[l].at[:2, :, :slab[kind].shape[3]].set(
                jnp.broadcast_to(slab[kind][l], (2,) + slab[kind].shape[2:]))
    t = jnp.asarray([31, 17, 0], jnp.int32)
    feed = jnp.asarray(tokens[np.asarray(t)][:, None], jnp.int32)
    lens = jnp.asarray([32, 18, 0], jnp.int32)
    step = jax.jit(m._step)
    stored = step(p, cache, feed, t, None, None, lens)
    relaid = step(m.burst_params(p), cache, feed, t, None, None, lens)
    for a, b in zip(jax.tree_util.tree_leaves(stored),
                    jax.tree_util.tree_leaves(relaid)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
    assert np.asarray(stored[0], np.float32)[:2].any()
    assert np.asarray(stored[2]).tolist()[4] == 2      # the chunk lane 0 ended


def test_the_batchers_burst_runs_on_the_derived_tree_whoever_calls_it(
        model, params, tokens, batcher):
    """A caller that drives the batcher's own ``_burst_fn`` with the
    batcher's params (the benchmark's comparison does) gets the executable
    the scheduler runs, on the derived tree, and compiles no second one; a
    tree of its own goes through as it is, to the same tokens."""
    relaid = 4 * (sum(p[w].size for p in params["layers"]
                      for w in ("wq", "wk", "wv")) + params["unembed"].size)
    assert batcher.stats["burst_params_relaid_bytes"] == relaid
    assert batcher.params is params and batcher._burst_params is not params
    live = jnp.asarray([True, True, False, True, False, False])
    zeros = jnp.zeros((6,), jnp.float32)

    def burst(tree):
        cache, (cur_tok, pos, keys), _ = _admit(
            batcher, params, tokens, {0: 6, 1: 30, 3: 94})
        toks, *_rest, cache, _keys, _counts = batcher._burst_fn(
            tree, cache, cur_tok, pos, live, zeros, keys, 5, None)
        batcher._cache = cache
        return np.asarray(toks)

    mine = burst(params)
    assert batcher._burst_fn._cache_size() == 1
    np.testing.assert_array_equal(burst(batcher._burst_params), mine)
    assert batcher._burst_fn._cache_size() == 1
    np.testing.assert_array_equal(burst(dict(params)), mine)
    assert batcher._burst_fn._cache_size() == 2
