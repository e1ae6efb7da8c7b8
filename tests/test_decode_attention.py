"""Ragged decode-attention kernel: interpret-mode equivalence on the CPU.

The kernel's math is checked against ``DecoderLM._cache_attention`` (the
two dots it replaces on a TPU) at small shapes, and the dispatching entry
is checked to take the dots here and the kernel when lowered for a TPU.
The Mosaic compile at the benchmark's widths is ``tests/test_burst_hlo.py``
(the one file that loads the TPU compiler); speed is the chip's to say.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from seldon_core_tpu.models.llm import DecoderLM
from seldon_core_tpu.ops.decode_attention import (
    BLOCK,
    decode_attention,
    ragged_decode_attention,
    reads_ragged,
)

BLK = 128  # the tests' block: three of them make the cache
T = 3 * BLK
# every edge of a block, idle and full, mixed in one batch
LENS = (0, 1, BLK - 1, BLK, BLK + 1, T)


def _inputs(rep, dtype, t=T, lanes=len(LENS), kv=2, dh=128, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (lanes, kv * rep, 1, dh), dtype)
    k = jax.random.normal(ks[1], (lanes, kv, t, dh), dtype)
    v = jax.random.normal(ks[2], (lanes, kv, t, dh), dtype)
    return q, k, v


def _dots(q, k, v, lens):
    """What the burst computed before the kernel: the two dots over the
    whole cache under the ``key_pos <= len - 1`` mask; an idle lane's row
    is defined as zeros."""
    o = DecoderLM._cache_attention(q, k, v, lens - 1, q.dtype)
    return jnp.where(lens[:, None, None, None] > 0, o, 0)


# Tolerance. float32: both sides accumulate in float32 and differ only in
# the order of the softmax's sums (online, per block), a few ulps: 1e-5.
# bfloat16 (the served precision): the dots round the normalised weights
# to bfloat16 before the second dot, the kernel rounds the unnormalised
# ones and divides after, and both round the output: two bfloat16 ulps of
# an output below 2, 2 x 2**-7.
@pytest.mark.parametrize("rep", [1, 2, 4])
@pytest.mark.parametrize("dtype,tol", [("float32", 1e-5), ("bfloat16", 2 ** -6)])
def test_kernel_matches_the_dots(rep, dtype, tol):
    q, k, v = _inputs(rep, jnp.dtype(dtype))
    lens = jnp.asarray(LENS, jnp.int32)
    got = ragged_decode_attention(q, k, v, lens, block=BLK, interpret=True)
    ref = _dots(q, k, v, lens)
    assert got.shape == q.shape and got.dtype == q.dtype
    err = jnp.abs(got.astype(jnp.float32) - ref.astype(jnp.float32))
    assert float(err.max()) <= tol, err.max(axis=(1, 2, 3))
    # a lane of length 0 reads nothing and gives zeros, exactly
    assert not np.asarray(got[0], np.float32).any()


@pytest.mark.parametrize("block", [128, 256])
def test_kernel_block_sizes_agree(block):
    q, k, v = _inputs(2, jnp.bfloat16, t=512)
    lens = jnp.asarray([0, 1, 255, 256, 257, 512], jnp.int32)
    got = ragged_decode_attention(q, k, v, lens, block=block, interpret=True)
    err = jnp.abs(got.astype(jnp.float32)
                  - _dots(q, k, v, lens).astype(jnp.float32))
    assert float(err.max()) <= 2 ** -6


@pytest.mark.parametrize("rep", [1, 2, 4])
def test_a_lane_depends_on_its_own_keys_alone(rep):
    """Lane 2's output is bit-equal whatever the other lanes' lengths,
    whichever lanes are idle, and however long the cache (the bucket) is
    beyond the lane's own length."""
    q, k, v = _inputs(rep, jnp.bfloat16)
    mine = BLK + 37
    runs = []
    for others in ((0, 0, 0, 0, 0), (T, 1, BLK, 5, 0), (3, T, T, T, T)):
        lens = list(others)
        lens.insert(2, mine)
        runs.append(ragged_decode_attention(
            q, k, v, jnp.asarray(lens, jnp.int32), block=BLK, interpret=True)[2])
    # a shorter cache holding the same keys: the bound moved, the lane not
    runs.append(ragged_decode_attention(
        q, k[:, :, :2 * BLK], v[:, :, :2 * BLK],
        jnp.asarray([9, 9, mine, 9, 9, 9], jnp.int32),
        block=BLK, interpret=True)[2])
    for other in runs[1:]:
        assert np.array_equal(np.asarray(runs[0], np.float32),
                              np.asarray(other, np.float32))


def test_lengths_are_clamped_to_the_cache():
    q, k, v = _inputs(2, jnp.bfloat16)
    over = ragged_decode_attention(
        q, k, v, jnp.asarray([-3, T + 500, T, 0, 1, 2], jnp.int32),
        block=BLK, interpret=True)
    ref = ragged_decode_attention(
        q, k, v, jnp.asarray([0, T, T, 0, 1, 2], jnp.int32),
        block=BLK, interpret=True)
    assert np.array_equal(np.asarray(over, np.float32),
                          np.asarray(ref, np.float32))


def test_kernel_rejects_shapes_it_cannot_tile():
    q, k, v = _inputs(2, jnp.bfloat16, t=BLK + 16)
    with pytest.raises(ValueError, match="do not fit"):
        ragged_decode_attention(q, k, v, jnp.zeros((len(LENS),), jnp.int32),
                                block=BLK)


@pytest.mark.parametrize("attn_len", [None, 2 * BLOCK, 10 * BLOCK])
def test_entry_takes_the_dots_off_tpu(attn_len):
    """On this backend the entry is the parent's read, bit for bit: the
    dots over the bucket's slice under ``key_pos <= pos``, idle lanes
    (``lens == 0``) computed from their stale position like the rest."""
    t = 3 * BLOCK
    q, k, v = _inputs(2, jnp.bfloat16, t=t)
    bound = t if attn_len is None else min(attn_len, t)
    pos = jnp.asarray([7, 0, BLOCK - 1, BLOCK, bound - 2, bound - 1], jnp.int32)
    lens = jnp.where(jnp.arange(len(LENS)) == 0, 0, pos + 1)
    got = decode_attention(q, k, v, pos, lens, attn_len=attn_len)
    ref = DecoderLM._cache_attention(
        q, k[:, :, :bound], v[:, :, :bound], pos, q.dtype)
    assert np.array_equal(np.asarray(got, np.float32),
                          np.asarray(ref, np.float32))
    lowered = jax.jit(
        lambda *a: decode_attention(*a, attn_len=attn_len)
    ).lower(q, k, v, pos, lens).as_text()
    assert "tpu_custom_call" not in lowered


def _entry_args(**kwargs):
    q, k, v = _inputs(2, jnp.bfloat16, **kwargs)
    lens = jnp.asarray(LENS, jnp.int32)
    return q, k, v, jnp.maximum(lens - 1, 0), lens


def test_entry_picks_the_kernel_by_the_platform_it_is_lowered_for():
    """``jax.default_backend()`` is the CPU here; lowered for a TPU the
    same call holds the Mosaic kernel, and no dot over the cache."""
    args = _entry_args(t=2 * BLOCK)
    fn = jax.jit(lambda *a: decode_attention(*a, attn_len=BLOCK))
    assert jax.default_backend() != "tpu"
    mlir = jax.export.export(fn, platforms=["tpu"])(*args).mlir_module()
    assert mlir.count("tpu_custom_call") == 1
    assert "dot_general" not in mlir


@pytest.mark.parametrize("why,kwargs", [
    ("a window of queries", dict(t_q=2)),
    ("a head_dim off the lane width", dict(dh=64)),
    ("a cache the block does not divide", dict(t=BLOCK + BLOCK // 2)),
])
def test_entry_keeps_the_dots_where_the_kernel_does_not_tile(why, kwargs):
    kwargs = {"t": 2 * BLOCK, **kwargs}
    t_q = kwargs.pop("t_q", 1)
    q, *rest = _entry_args(**kwargs)
    q = jnp.repeat(q, t_q, axis=2)
    mlir = jax.export.export(
        jax.jit(decode_attention), platforms=["tpu"])(q, *rest).mlir_module()
    assert "tpu_custom_call" not in mlir, why


_BF16 = jnp.dtype("bfloat16")


@pytest.mark.parametrize("why,change,want", [
    ("a TPU, heads that tile", {}, True),
    ("the CPU", dict(platform="cpu"), False),
    ("a serving mesh", dict(mesh=object()), False),
    ("a head_dim off the lane width", dict(dh=64), False),
    ("mixed dtypes", dict(dtypes=(_BF16, _BF16, jnp.dtype("float32"))), False),
    ("a cache the block does not divide", dict(t=BLOCK + BLOCK // 2), False),
    ("a window of queries", dict(t_q=2), False),
    ("heads that are no multiple of the KV heads", dict(heads=3), False),
])
def test_the_rule_for_a_ragged_read(why, change, want):
    """``reads_ragged``: the one place that says where the decode read
    takes each lane's own length. The scheduler asks it whether a burst
    needs a bucket; ``decode_attention()`` asks it whether to hand the
    lowering the kernel."""
    c = dict(platform="tpu", mesh=None, dh=128, t=2 * BLOCK, t_q=1, heads=4,
             dtypes=(_BF16,) * 3)
    c.update(change)
    got = reads_ragged(
        c["platform"], (6, c["heads"], c["t_q"], c["dh"]),
        (6, 2, c["t"], c["dh"]), c["dtypes"], c["mesh"])
    assert got is want, why


@pytest.fixture()
def on_a_tpu(monkeypatch):
    """The entry as a lowering for a TPU runs it: ``platform_dependent``
    takes its ``tpu`` branch and the kernel runs interpreted. Returns the
    entry's module and the entry, unjitted (the jitted one would answer
    from a trace made before the patches)."""
    import importlib

    mod = importlib.import_module("seldon_core_tpu.ops.decode_attention")
    monkeypatch.setattr(
        mod.lax, "platform_dependent",
        lambda *args, tpu, default: tpu(*args))
    monkeypatch.setattr(
        mod, "ragged_decode_attention",
        lambda *a, **kw: ragged_decode_attention(*a, **kw, interpret=True))
    return mod, mod.decode_attention.__wrapped__


def test_entry_takes_its_choice_from_the_rule(on_a_tpu, monkeypatch):
    """Heads that tile, lowered for a TPU: the kernel. The same call once
    the rule says no: the dots, bit for bit, and the rule was asked with
    what the entry can see."""
    mod, entry = on_a_tpu
    q, k, v, pos, lens = _entry_args(t=2 * BLOCK)
    dots = DecoderLM._cache_attention(q, k, v, pos, q.dtype)
    kernel = entry(q, k, v, pos, lens)
    assert not np.asarray(kernel[0], np.float32).any()  # lens[0] == 0
    assert np.asarray(dots[0], np.float32).any()
    asked = []

    def no(*args):
        asked.append(args)
        return False

    monkeypatch.setattr(mod, "reads_ragged", no)
    got = entry(q, k, v, pos, lens, mesh=None)
    assert np.array_equal(np.asarray(got, np.float32),
                          np.asarray(dots, np.float32))
    assert asked == [("tpu", q.shape, k.shape, (q.dtype,) * 3, None)]


@pytest.mark.parametrize("lens", [
    (0, 1, BLOCK - 1, BLOCK, BLOCK + 1, 2 * BLOCK),
    (0, 0, 0, 0, 0, 2 * BLOCK),
    (2 * BLOCK, 0, 2 * BLOCK, 7, 2 * BLOCK, 0),
], ids=["every_edge", "one_lane_at_the_bucket", "full_and_idle"])
def test_kernel_without_a_bucket_is_the_bucketed_one_bit_for_bit(on_a_tpu, lens):
    """What the batcher relies on where the read is ragged: the bucket is
    a clamp on lengths that never exceed it, so ``attn_len=None`` (the
    clamp is the cache's length) gives the bucket's outputs exactly, idle
    lanes and lanes at the bucket itself included."""
    _, entry = on_a_tpu
    q, k, v = _inputs(2, jnp.bfloat16, t=3 * BLOCK, seed=3)
    lens = jnp.asarray(lens, jnp.int32)
    pos = jnp.maximum(lens - 1, 0)
    bucketed = entry(q, k, v, pos, lens, attn_len=2 * BLOCK)
    free = entry(q, k, v, pos, lens, attn_len=None)
    assert np.array_equal(np.asarray(free, np.float32),
                          np.asarray(bucketed, np.float32))
    ref = _dots(q, k, v, lens)
    err = jnp.abs(free.astype(jnp.float32) - ref.astype(jnp.float32))
    assert float(err.max()) <= 2 ** -6


def _tiny_model():
    model = DecoderLM(
        vocab_size=128, d_model=256, n_layers=2, n_heads=2, n_kv_heads=1,
        d_ff=256, max_seq=2 * BLOCK,
    )
    params = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16), model.init_params(0))
    return model, params


def _step_inputs(model):
    cfg = model.cfg
    lanes, t = 4, 2 * BLOCK
    ks = jax.random.split(jax.random.PRNGKey(4), 2 * cfg.n_layers)
    shape = (lanes, cfg.n_kv_heads, t, cfg.head_dim)
    cache_k = [jax.random.normal(x, shape, jnp.bfloat16) for x in ks[:cfg.n_layers]]
    cache_v = [jax.random.normal(x, shape, jnp.bfloat16) for x in ks[cfg.n_layers:]]
    tokens = jnp.asarray([[3], [5], [7], [11]], jnp.int32)
    pos = jnp.asarray([17, 300, 40, 0], jnp.int32)
    active = jnp.asarray([True, False, True, False])
    return cache_k, cache_v, tokens, pos, active


def test_lens_leave_the_step_as_it_was_off_tpu():
    """``fused_step`` passes ``lens = where(active, pos + 1, 0)``. Here the
    dots compute idle lanes too, from ``pos``: logits and what is written
    are byte-equal to the step without ``lens``, on every lane."""
    model, params = _tiny_model()
    cache_k, cache_v, tokens, pos, active = _step_inputs(model)
    step = jax.jit(model.decode_step_ragged_list, static_argnames=("attn_len",))
    base, bk, bv = step(params, cache_k, cache_v, tokens, pos, attn_len=384)
    got, gk, gv = step(params, cache_k, cache_v, tokens, pos, attn_len=384,
                       lens=jnp.where(active, pos + 1, 0))
    assert np.array_equal(np.asarray(base), np.asarray(got))
    for a, b in zip(bk + bv, gk + gv):
        assert np.array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32))


def test_model_step_through_the_kernel(monkeypatch):
    """The model's ragged step with the kernel (interpreted) in the place
    the entry gives it on a TPU: the live lanes' logits agree with the
    dots' step to bfloat16 rounding through two layers, and are
    byte-equal whether the idle lanes read their stale positions or
    nothing."""
    import seldon_core_tpu.ops as ops

    def through_kernel(q, k, v, pos, lens, attn_len=None, mesh=None):
        assert attn_len == 384 and mesh is None
        return ragged_decode_attention(
            q, k, v, jnp.minimum(lens, attn_len), block=BLOCK, interpret=True)

    model, params = _tiny_model()
    cache_k, cache_v, tokens, pos, active = _step_inputs(model)
    live = np.asarray(active)
    dots, _, _ = model.decode_step_ragged_list(
        params, cache_k, cache_v, tokens, pos, attn_len=384)
    monkeypatch.setattr(ops, "decode_attention", through_kernel)
    every, ek, _ = model.decode_step_ragged_list(
        params, cache_k, cache_v, tokens, pos, attn_len=384)
    some, sk, _ = model.decode_step_ragged_list(
        params, cache_k, cache_v, tokens, pos, attn_len=384,
        lens=jnp.where(active, pos + 1, 0))
    assert np.array_equal(np.asarray(every)[live], np.asarray(some)[live])
    assert np.array_equal(np.asarray(ek[-1], np.float32)[live],
                          np.asarray(sk[-1], np.float32)[live])
    spread = float(jnp.std(dots))
    assert float(jnp.abs(dots - some)[live].max()) < 0.05 * spread


@pytest.mark.parametrize("pos,k,bucket,want", [
    (0, 1, 128, BLOCK),                       # one key is one block
    (BLOCK - 1, 1, 2 * BLOCK, BLOCK),         # the block's last position
    (BLOCK, 1, 2 * BLOCK, 2 * BLOCK),         # the next block's first
    (BLOCK - 2, 4, 4 * BLOCK, 2 * BLOCK + 2 * 2 * BLOCK),  # crossing inside
    (3 * BLOCK, 8, 2 * BLOCK, 8 * 2 * BLOCK),  # never past the bucket
])
def test_positions_streamed_rounds_each_step_to_the_block(pos, k, bucket, want):
    from seldon_core_tpu.serving.continuous import _positions_streamed

    assert _positions_streamed(pos, k, bucket, BLOCK) == want


def test_batcher_counts_what_the_read_streams_and_what_the_bucket_held():
    """``kv_positions_read`` / ``kv_positions_bucket`` in ``stats`` (and so
    in a capture's counters): per dispatched burst, what the ragged read
    streams for the lanes active against rows x attn_len x steps."""
    from seldon_core_tpu.serving.continuous import (
        ContinuousBatcher,
        _positions_streamed,
    )

    model = DecoderLM(vocab_size=256, d_model=32, n_layers=2, n_heads=4,
                      n_kv_heads=2, d_ff=64, max_seq=4 * BLOCK, dtype="float32")
    b = ContinuousBatcher(model, model.init_params(0), slots=4,
                          max_seq=4 * BLOCK, prefill_buckets=(8, 16),
                          steps_per_poll=2)
    b.trace_groups = []
    try:
        assert b.stats["kv_positions_read"] == b.stats["kv_positions_bucket"] == 0
        out = b.submit([3, 17, 42, 99, 7], max_new_tokens=9).result(timeout=120)
        assert len(out) == 5 + 9
        stats, groups = dict(b.stats), list(b.trace_groups)
    finally:
        b.close()
    assert groups and {len(g["lanes"]) for g in groups} == {1}
    # one lane of four, a few positions deep: a block a step, whatever the
    # bucket; the dots read the bucket of all four rows
    assert stats["kv_positions_bucket"] == sum(
        2 * 4 * g["attn_len"] for g in groups)
    assert stats["kv_positions_read"] == len(groups) * _positions_streamed(5, 2, 128, BLOCK)
    assert stats["kv_positions_read"] == 2 * BLOCK * len(groups)
    assert stats["kv_positions_read"] * 4 <= stats["kv_positions_bucket"]
    assert "kv_positions_read" in b.capture_counters()["counters"]
