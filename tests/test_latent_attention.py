"""``ops/latent_attention.py``: the ragged latent decode kernel, interpreted
on the CPU, against the scatter and the two dots it stands for: idle lanes,
no live lane, lengths on both sides of a block's edge, the new row landed
bit-equal to the spelt-out update, a write outside what the lane reads, and
the rule that chooses the kernel. Its compile for a v5e, inside the
family's burst: ``tests/test_burst_hlo.py``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from seldon_core_tpu.ops.decode_attention import GROUP
from seldon_core_tpu.ops.latent_attention import (
    LATENT_BLOCK, latent_cache_attention, latent_cache_write, latent_decode_attention,
    latent_reads_ragged, ragged_latent_attention, row_width)

H, W, T, RANK = 4, 256, 384, 128
SCALE = 0.2
# the cases are written in blocks of 128 (T is three of them); the served
# block runs them scaled to its own edges
BLOCKS = [128, LATENT_BLOCK]


def _case(lens, seed=0, dtype=jnp.bfloat16, heads=H, t=T):
    rng = np.random.default_rng(seed)
    b = len(lens)
    q = jnp.asarray(rng.normal(size=(b, heads, W)), dtype)
    cache = jnp.asarray(rng.normal(size=(b, t, W)), dtype)
    new = jnp.asarray(rng.normal(size=(b, W)), dtype)
    return q, cache, new, jnp.asarray(lens, jnp.int32)


def _spelt_out(q, cache, new, lens, write_pos):
    """Every lane with ``lens > 0`` takes its row by a plain indexed
    update, then attends to its first ``lens`` positions in float32."""
    cache = np.asarray(cache).copy()
    out = np.zeros((q.shape[0], q.shape[1], RANK), np.float32)
    for b, (n, wp) in enumerate(zip(np.asarray(lens), np.asarray(write_pos))):
        if n <= 0:
            continue
        if 0 <= wp < cache.shape[1]:
            cache[b, wp] = np.asarray(new)[b]
        rows = cache[b, :n].astype(np.float32)
        s = np.asarray(q, np.float32)[b] @ rows.T * SCALE
        p = np.exp(s - s.max(-1, keepdims=True))
        p = (p / p.sum(-1, keepdims=True)).astype(np.asarray(q).dtype)
        out[b] = p.astype(np.float32) @ rows[:, :RANK]
    return out, cache


def test_a_row_is_the_latent_and_the_rotary_key_in_whole_registers():
    assert row_width(512, 64) == 640
    assert row_width(128, 16) == 256
    assert row_width(128, 128) == 256


def _at_edges_of(block, lens):
    """``lens`` written for blocks of 128, moved to the same side of the
    same edge of ``block``: 130 -> block + 2, 127 -> block - 1."""
    return [n if n < 64 else (n + 64) // 128 * block + (n + 64) % 128 - 64
            for n in lens]


@pytest.mark.parametrize("block", BLOCKS)
@pytest.mark.parametrize("lens", [
    [130, 0, 128, 1, 384],          # over an edge, idle, on it, one, the cache
    [127, 129, 256, 257, 0, 0, 5],  # under and over each edge; idle lanes last
    [0, 0, 200],                    # idle lanes first
    [384],                          # one lane, every block
], ids=["mixed", "edges", "idle_first", "full"])
def test_the_kernel_is_the_write_then_the_read(lens, block):
    q, cache, new, lens = _case(_at_edges_of(block, lens), t=3 * block)
    write_pos = jnp.maximum(lens - 1, 0)
    o, after = ragged_latent_attention(
        q, cache, lens, new, write_pos, rank=RANK, scale=SCALE, block=block,
        interpret=True)
    want, want_cache = _spelt_out(q, cache, new, lens, write_pos)
    live = np.asarray(lens) > 0
    np.testing.assert_allclose(np.asarray(o, np.float32)[live], want[live],
                               atol=0.03, rtol=0.02)
    # the new row where the spelt-out update put it, bit for bit, and every
    # other position of every lane as it was
    np.testing.assert_array_equal(np.asarray(after), want_cache)
    # an idle lane: zeros out, nothing read, nothing written
    assert not np.asarray(o, np.float32)[~live].any()
    np.testing.assert_array_equal(np.asarray(after)[~live],
                                  np.asarray(cache)[~live])


def test_no_live_lane_reads_and_writes_nothing():
    q, cache, new, lens = _case([0, 0, 0, 0])
    o, after = ragged_latent_attention(
        q, cache, lens, new, jnp.zeros((4,), jnp.int32), rank=RANK,
        scale=SCALE, block=128, interpret=True)
    assert not np.asarray(o, np.float32).any()
    np.testing.assert_array_equal(np.asarray(after), np.asarray(cache))


def test_a_write_outside_the_read_still_lands_and_one_outside_the_cache_is_dropped():
    q, cache, new, lens = _case([100, 100, 100, 260])
    # inside the cache past the read; parked out of bounds; negative; in an
    # earlier block of the lane's read than its last
    write_pos = jnp.asarray([300, T, -1, 3], jnp.int32)
    o, after = ragged_latent_attention(
        q, cache, lens, new, write_pos, rank=RANK, scale=SCALE, block=128,
        interpret=True)
    want, want_cache = _spelt_out(q, cache, new, lens, write_pos)
    np.testing.assert_array_equal(np.asarray(after), want_cache)
    np.testing.assert_allclose(np.asarray(o, np.float32), want, atol=0.03, rtol=0.02)
    # the scatter drops the same two
    np.testing.assert_array_equal(
        np.asarray(latent_cache_write(cache, new, write_pos)), want_cache)


@pytest.mark.parametrize("attn_len", [None, 256])
def test_the_dots_and_the_kernel_agree_where_a_lane_is_live(attn_len):
    lens = [130, 0, 128, 1, 256]
    q, cache, new, lens = _case(lens, seed=3)
    pos = jnp.maximum(lens - 1, 0)
    o_d, c_d = latent_decode_attention(
        q, cache, new, pos, pos, lens, rank=RANK, scale=SCALE, attn_len=attn_len)
    o_k, c_k = ragged_latent_attention(
        q, cache, jnp.minimum(lens, attn_len or T), new, pos, rank=RANK,
        scale=SCALE, block=128, interpret=True)
    live = np.asarray(lens) > 0
    np.testing.assert_allclose(np.asarray(o_d, np.float32)[live],
                               np.asarray(o_k, np.float32)[live], atol=0.03, rtol=0.02)
    np.testing.assert_array_equal(np.asarray(c_d)[live], np.asarray(c_k)[live])
    # in float32 the two are one function to rounding
    q32, c32, n32 = (a.astype(jnp.float32) for a in (q, cache, new))
    o_d, _ = latent_decode_attention(
        q32, c32, n32, pos, pos, lens, rank=RANK, scale=SCALE, attn_len=attn_len)
    o_k, _ = ragged_latent_attention(
        q32, c32, jnp.minimum(lens, attn_len or T), n32, pos, rank=RANK,
        scale=SCALE, block=128, interpret=True)
    np.testing.assert_allclose(np.asarray(o_d)[live], np.asarray(o_k)[live], atol=2e-5)


def test_the_dots_read_the_row_as_key_and_its_latent_as_value():
    q, cache, _new, _lens = _case([9], seed=4, dtype=jnp.float32)
    out = latent_cache_attention(q, cache[:, :16], jnp.asarray([8]), RANK, SCALE)
    rows = np.asarray(cache)[0, :9]
    s = np.asarray(q)[0] @ rows.T * SCALE
    p = np.exp(s - s.max(-1, keepdims=True))
    np.testing.assert_allclose(out[0], (p / p.sum(-1, keepdims=True)) @ rows[:, :RANK],
                               atol=1e-5)


def test_the_rule_that_chooses_the_kernel():
    shapes = ((8, 32, 640), (8, 6144, 640), (jnp.bfloat16, jnp.bfloat16), 512)
    assert latent_reads_ragged("tpu", *shapes)
    assert not latent_reads_ragged("cpu", *shapes)
    assert not latent_reads_ragged("tpu", *shapes, mesh=object())
    assert not latent_reads_ragged(
        "tpu", (8, 32, 640), (8, 6100, 640), shapes[2], 512)     # a part block
    assert LATENT_BLOCK == 512 and not latent_reads_ragged(
        "tpu", (8, 32, 640), (8, 6272, 640), shapes[2], 512)     # 49 x 128
    assert not latent_reads_ragged(
        "tpu", (8, 32, 576), (8, 6144, 576), shapes[2], 512)     # a part register
    assert not latent_reads_ragged(
        "tpu", shapes[0], shapes[1], (jnp.float32, jnp.bfloat16), 512)
    with pytest.raises(ValueError):
        ragged_latent_attention(
            jnp.zeros((1, 4, 256)), jnp.zeros((1, 100, 256)), jnp.zeros((1,), jnp.int32),
            jnp.zeros((1, 256)), jnp.zeros((1,), jnp.int32), rank=128, scale=1.0,
            interpret=True)
    assert LATENT_BLOCK % GROUP == 0


def test_the_step_is_lowered_under_its_scope():
    q, cache, new, lens = _case([5, 0])
    text = jax.jit(lambda *a: latent_decode_attention(
        *a, rank=RANK, scale=SCALE)).lower(
            q, cache, new, lens, lens, lens).as_text(debug_info=True)
    assert "latent_decode_attention" in text


@pytest.mark.parametrize("window", [None, 96])
def test_the_flash_kernel_takes_values_narrower_than_keys(window):
    """The latent family's prefill: keys of nope + rope, values of
    v_head_dim; the scale is the keys', the output the values' width."""
    from seldon_core_tpu.ops.flash_attention import (
        _xla_attention, attention, flash_attention)

    rng = np.random.default_rng(6)
    q = jnp.asarray(rng.normal(size=(2, 3, 256, 48)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, 3, 256, 48)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, 3, 256, 32)), jnp.float32)
    got = flash_attention(q, k, v, causal=True, interpret=True, window=window,
                          name="latent_prefill_attention")
    want = _xla_attention(q, k, v, True, None, window)
    assert got.shape == want.shape == (2, 3, 256, 32)
    np.testing.assert_allclose(got, want, atol=2e-5)
    np.testing.assert_allclose(
        attention(q, k, v, causal=True, window=window,
                  name="latent_prefill_attention"), want, atol=2e-5)
    # equal widths: what it was
    same = flash_attention(q, k, k, causal=True, interpret=True)
    np.testing.assert_allclose(same, _xla_attention(q, k, k, True), atol=2e-5)
