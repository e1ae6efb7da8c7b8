"""``ops/eva_attention.py``: the ragged decode kernel over a window ring and
its summary rows, run interpreted on the CPU, against the scatters, the
dots and the pooling of the chunk a step completes; the pooling; and the flash kernel behind a visible prefix
(``ops/flash_attention.py``), interpreted, against its masked dots.

    JAX_PLATFORMS=cpu python -m pytest tests/test_eva_attention.py -q
"""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from seldon_core_tpu.ops import eva_attention as eva
from seldon_core_tpu.ops.decode_attention import cache_write
from seldon_core_tpu.ops.flash_attention import (
    _prefixed_attention, attention, flash_attention)

B, H, W, NS, DH, C = 6, 2, 256, 384, 128, 16
SCALE = 1.0 / np.sqrt(DH)


def _draw(seed=0, dtype=jnp.bfloat16):
    rng = np.random.default_rng(seed)

    def r(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32).astype(dtype)

    return dict(q=r(B, H, DH), ring_k=r(B, H, W, DH), ring_v=r(B, H, W, DH),
                sum_k=r(B, H, NS, DH), sum_v=r(B, H, NS, DH),
                k_new=r(B, H, DH), v_new=r(B, H, DH),
                mu=r(H, DH).astype(jnp.float32), phi=r(H, DH).astype(jnp.float32))


def _spelt_out(d, n_ring, n_sum, wp, sum_at):
    """The step as the scatters, the dots and ``chunk_summary`` make it: a
    lane that reads nothing writes nothing, one whose ring write is dropped
    pools nothing."""
    wp = jnp.where(n_ring > 0, wp, W)
    ring_k = cache_write(d["ring_k"], d["k_new"][:, :, None], wp[:, None])
    ring_v = cache_write(d["ring_v"], d["v_new"][:, :, None], wp[:, None])
    o = eva.eva_cache_attention(
        d["q"], ring_k, ring_v, d["sum_k"], d["sum_v"], n_ring,
        jnp.where(n_ring > 0, n_sum, 0), SCALE)
    sum_k, sum_v = np.array(d["sum_k"]), np.array(d["sum_v"])
    for b in range(B):
        if 0 <= int(wp[b]) < W and 0 <= int(sum_at[b]) < NS:
            at = int(wp[b]) // C * C
            pooled_k, pooled_v = eva.chunk_summary(
                ring_k[b, :, None, at:at + C], ring_v[b, :, None, at:at + C],
                d["mu"], d["phi"], SCALE)
            sum_k[b, :, int(sum_at[b])] = pooled_k[:, 0]
            sum_v[b, :, int(sum_at[b])] = pooled_v[:, 0]
    return o, ring_k, ring_v, jnp.asarray(sum_k), jnp.asarray(sum_v)


def _kernel(d, n_ring, n_sum, wp, sum_at):
    return eva.ragged_eva_attention(
        d["q"], d["ring_k"], d["ring_v"], d["sum_k"], d["sum_v"], n_ring,
        n_sum, d["k_new"], d["v_new"], wp, d["mu"], d["phi"], sum_at,
        scale=SCALE, chunk=C, interpret=True)


def _one_rounding(got, want):
    """Equal to one rounding of bfloat16 (a pooled row's sums are float32
    in another order)."""
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    np.testing.assert_array_less(
        np.abs(got - want), 2.0 ** -7 * np.maximum(np.abs(want), 2.0 ** -6))


NONE = NS   # a ``sum_at`` that names no row

CASES = {
    # an idle lane among live ones; a ring of ONE row; lengths on both
    # sides of a block's edge in both kinds; a full ring and all summaries;
    # no step ends a chunk
    "mixed": ([0, 1, 128, 129, 256, 77], [128, 0, 128, 256, 384, 128],
              [5, 0, 127, 128, 255, 76], [NONE] * B),
    # a live lane whose write is parked outside the ring
    "parked": ([3, 0, 200, 0, 129, 1], [0, 0, 256, 128, 128, 384],
               [W, 3, W + 7, 0, 128, 0], [NONE] * B),
    "none_live": ([0] * B, [128] * B, [0, 1, 2, 3, 4, 5], [NONE] * B),
    # steps that end a chunk beside steps that end none, a parked lane and
    # one that reads nothing, both told a row: the first writes row t // 16
    # of both kinds, the others nothing; the row first and last of its
    # group, in the array's first and last block
    "some_end_a_chunk": ([0, 16, 128, 144, 256, 80], [128, 0, 128, 256, 384, 0],
                         [15, 15, 127, W + 15, 255, 78],
                         [8, 0, 7, 200, NS - 1, NONE]),
    # ALL lanes end a chunk in one call (the cell's lanes run in lock step)
    "all_end_a_chunk": ([16, 32, 128, 144, 256, 80], [128, 0, 128, 256, 384, 128],
                        [15, 31, 127, 143, 255, 79],
                        [0, 15, 16, NS - 16, NS - 1, 133]),
    # the chunk's rows come from no block the lane reads (``land_unread``):
    # the row is written past the rows read
    "unread_chunk": ([1, 16, 100, 1, 256, 64], [0] * B,
                     [143, 255, 239, 15, 255, 63], [8, 15, 14, 0, 383, 3]),
    # a told row that is out of range either way names none
    "rows_out_of_range": ([16, 32, 48, 64, 80, 96], [128] * B,
                          [15, 31, 47, 63, 79, 95],
                          [-1, NS, NS + 5, -NS, 2 * NS, NONE]),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_kernel_is_the_scatter_and_the_dots(case):
    n_ring, n_sum, wp, sum_at = (jnp.asarray(a, jnp.int32) for a in CASES[case])
    d = _draw(1)
    o, ring_k, ring_v, sum_k, sum_v = _kernel(d, n_ring, n_sum, wp, sum_at)
    want_o, want_k, want_v, want_sk, want_sv = _spelt_out(
        d, n_ring, n_sum, wp, sum_at)
    # the new row landed bit-equal to the spelt-out update, and nothing
    # else of the ring moved
    assert bool((ring_k == want_k).all()) and bool((ring_v == want_v).all())
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(want_o, np.float32), atol=1e-2, rtol=1e-2)
    for b in range(B):
        pools = (int(n_ring[b]) > 0 and 0 <= int(wp[b]) < W
                 and 0 <= int(sum_at[b]) < NS)
        if int(n_ring[b]) == 0:
            # a lane that reads nothing: zeros out, and its ring untouched
            assert not np.asarray(o[b], np.float32).any()
            assert bool((ring_k[b] == d["ring_k"][b]).all())
            assert bool((ring_v[b] == d["ring_v"][b]).all())
        elif 0 <= int(wp[b]) < W:
            # a writing lane's row is in the ring
            assert bool((ring_k[b, :, int(wp[b])] == d["k_new"][b]).all())
        else:
            # parked: reads, and the ring stays as it was
            assert bool((ring_k[b] == d["ring_k"][b]).all())
        keep = np.ones(NS, bool)
        if pools:
            # the lane that ends a chunk: row ``sum_at`` of BOTH kinds is
            # ``chunk_summary`` of its patched chunk
            at = int(sum_at[b])
            keep[at] = False
            _one_rounding(sum_k[b, :, at], want_sk[b, :, at])
            _one_rounding(sum_v[b, :, at], want_sv[b, :, at])
            assert bool((sum_k[b, :, at] != d["sum_k"][b, :, at]).any())
            assert bool((sum_v[b, :, at] != d["sum_v"][b, :, at]).any())
        # every other summary row is bit for bit what went in
        assert bool((sum_k[b][:, keep] == d["sum_k"][b][:, keep]).all())
        assert bool((sum_v[b][:, keep] == d["sum_v"][b][:, keep]).all())


def test_a_windows_last_chunk_is_read_as_a_summary_by_the_next_call():
    """The step at ``t mod W == W - 1`` completes its window's last chunk
    and writes row ``t // C``; the next step, the next window's first,
    reads ``W / C`` summary rows more, that row the last of them."""
    d = _draw(6)
    per = W // C
    t = jnp.asarray([W - 1, 2 * W - 1, W - 1, 2 * W - 1, 3 * W - 1, W - 1],
                    jnp.int32)
    n_ring, n_sum = t % W + 1, t // W * per
    _, ring_k, ring_v, sum_k, sum_v = _kernel(d, n_ring, n_sum, t % W, t // C)
    want = _spelt_out(d, n_ring, n_sum, t % W, t // C)
    for b in range(B):
        _one_rounding(sum_k[b, :, int(t[b]) // C], want[3][b, :, int(t[b]) // C])
    # the next call: row 0 of the ring alone and the summaries grown by a
    # window's, read from the arrays the first call left
    nxt = dict(d, ring_k=ring_k, ring_v=ring_v, sum_k=sum_k, sum_v=sum_v)
    t = t + 1
    n_ring, n_sum = t % W + 1, t // W * per
    assert [int(n) for n in n_sum] == [per * (int(x) // W) for x in t]
    none = jnp.full((B,), NONE, jnp.int32)
    o, *_ = _kernel(nxt, n_ring, n_sum, t % W, none)
    want_o, *_ = _spelt_out(nxt, n_ring, n_sum, t % W, none)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(want_o, np.float32), atol=1e-2, rtol=1e-2)
    # and the row the first call wrote is seen: every lane's output moves
    # without it
    blind = dict(nxt, sum_k=d["sum_k"], sum_v=d["sum_v"])
    o_blind, *_ = _kernel(blind, n_ring, n_sum, t % W, none)
    moved = jnp.abs(o.astype(jnp.float32) - o_blind.astype(jnp.float32))
    assert float(moved.max(axis=(1, 2)).min()) > 1e-2


def test_both_kinds_are_under_one_softmax():
    """The summaries are not a second attention added to the first: a lane's
    output is the one softmax over ring and summary rows together."""
    d = _draw(2, jnp.float32)
    n_ring = jnp.asarray([40] * B, jnp.int32)
    n_sum = jnp.asarray([128] * B, jnp.int32)
    o = eva.eva_cache_attention(d["q"], d["ring_k"], d["ring_v"], d["sum_k"],
                                d["sum_v"], n_ring, n_sum, SCALE)
    keys = jnp.concatenate([d["ring_k"][:, :, :40], d["sum_k"][:, :, :128]], 2)
    vals = jnp.concatenate([d["ring_v"][:, :, :40], d["sum_v"][:, :, :128]], 2)
    p = jax.nn.softmax(jnp.einsum("bhd,bhtd->bht", d["q"], keys) * SCALE, -1)
    np.testing.assert_allclose(o, jnp.einsum("bht,bhtd->bhd", p, vals),
                               atol=2e-5)
    # with no summary visible it is the window's attention alone
    alone = eva.eva_cache_attention(
        d["q"], d["ring_k"], d["ring_v"], d["sum_k"], d["sum_v"], n_ring,
        jnp.zeros_like(n_sum), SCALE)
    p = jax.nn.softmax(jnp.einsum(
        "bhd,bhtd->bht", d["q"], d["ring_k"][:, :, :40]) * SCALE, -1)
    np.testing.assert_allclose(
        alone, jnp.einsum("bht,bhtd->bhd", p, d["ring_v"][:, :, :40]), atol=2e-5)


def test_the_entry_off_a_tpu_is_the_dots_and_pools_the_completed_chunk():
    d = _draw(3)
    n_ring = jnp.asarray([0, 1, 128, 144, 256, 80], jnp.int32)
    n_sum = jnp.asarray([128, 0, 128, 256, 384, 128], jnp.int32)
    wp = jnp.where(n_ring > 0, n_ring - 1, W)
    sum_at = jnp.asarray([NONE, NONE, 7, 200, NS - 1, 4], jnp.int32)
    o, ring_k, ring_v, sum_k, sum_v = eva.eva_decode_attention(
        d["q"], d["ring_k"], d["ring_v"], d["sum_k"], d["sum_v"], d["k_new"],
        d["v_new"], wp, n_ring, n_sum, d["mu"], d["phi"], sum_at, scale=SCALE,
        chunk=C)
    want_o, want_k, want_v, want_sk, want_sv = _spelt_out(
        d, n_ring, n_sum, wp, sum_at)
    assert bool((ring_k == want_k).all()) and bool((ring_v == want_v).all())
    np.testing.assert_array_equal(np.asarray(o, np.float32),
                                  np.asarray(want_o, np.float32))
    # the four lanes that end a chunk wrote their row of both kinds, and no
    # other row of any lane moved
    _one_rounding(sum_k, want_sk)
    _one_rounding(sum_v, want_sv)
    for got, src in ((sum_k, d["sum_k"]), (sum_v, d["sum_v"])):
        changed = np.asarray((got != src).any(axis=(1, 3)))
        assert changed.sum(axis=1).tolist() == [0, 0, 1, 1, 1, 1]
        assert all(changed[b, int(sum_at[b])] for b in range(2, B))
    # and the kernel's lowering keeps the entry's contract
    got = _kernel(d, n_ring, n_sum, wp, sum_at)
    assert bool((got[1] == ring_k).all()) and bool((got[2] == ring_v).all())
    _one_rounding(got[3], sum_k)
    _one_rounding(got[4], sum_v)


def test_which_shapes_take_the_kernel():
    q, ring, summ = (20, 32, 128), (20, 32, 2048, 128), (20, 32, 1024, 128)
    dts = (jnp.bfloat16,) * 5
    assert eva.eva_reads_ragged("tpu", q, ring, summ, dts, 16)
    assert not eva.eva_reads_ragged("cpu", q, ring, summ, dts, 16)
    assert not eva.eva_reads_ragged("tpu", q, ring, summ, dts, 16, mesh=object())
    assert not eva.eva_reads_ragged("tpu", q, ring, summ, dts, 4)
    assert not eva.eva_reads_ragged("tpu", (20, 32, 64), ring, summ, dts, 16)
    assert not eva.eva_reads_ragged("tpu", q, (20, 32, 32, 128), summ, dts, 16)
    assert not eva.eva_reads_ragged(
        "tpu", q, ring, summ, (jnp.float32,) + dts[1:], 16)
    with pytest.raises(ValueError, match="do not fit the kernel"):
        d = _draw(0)
        eva.ragged_eva_attention(
            d["q"], d["ring_k"][:, :, :100], d["ring_v"][:, :, :100],
            d["sum_k"], d["sum_v"], jnp.ones((B,), jnp.int32),
            jnp.zeros((B,), jnp.int32), d["k_new"], d["v_new"],
            jnp.zeros((B,), jnp.int32), d["mu"], d["phi"],
            jnp.zeros((B,), jnp.int32), scale=SCALE, chunk=C, interpret=True)


def test_a_chunks_summary_is_the_softmax_pooled_row():
    rng = np.random.default_rng(4)
    k = jnp.asarray(rng.standard_normal((3, H, 5, C, DH)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((3, H, 5, C, DH)), jnp.float32)
    mu = jnp.asarray(rng.standard_normal((H, DH)), jnp.float32)
    phi = jnp.asarray(rng.standard_normal((H, DH)), jnp.float32)
    pooled_k, pooled_v = eva.chunk_summary(k, v, mu, phi, SCALE)
    assert pooled_k.shape == pooled_v.shape == (3, H, 5, DH)
    for h in range(H):
        pk = jax.nn.softmax((k[1, h, 2] @ mu[h]) * SCALE)
        pv = jax.nn.softmax((k[1, h, 2] @ phi[h]) * SCALE)
        np.testing.assert_allclose(pooled_k[1, h, 2], pk @ k[1, h, 2], atol=1e-5)
        np.testing.assert_allclose(pooled_v[1, h, 2], pv @ v[1, h, 2], atol=1e-5)
    # zero pooling vectors: a plain mean of the chunk
    mean_k, mean_v = eva.chunk_summary(k, v, 0 * mu, 0 * phi, SCALE)
    np.testing.assert_allclose(mean_k, k.mean(-2), atol=1e-5)
    np.testing.assert_allclose(mean_v, v.mean(-2), atol=1e-5)


@pytest.mark.parametrize("visible", [0, 128, 200, 256])
def test_the_flash_kernel_behind_a_visible_prefix(visible):
    rng = np.random.default_rng(5)

    def r(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)

    q, k, v = r(1, 2, 256, 128), r(1, 2, 512, 128), r(1, 2, 512, 128)
    n = jnp.int32(visible)
    got = flash_attention(q, k, v, causal=True, prefix=256, prefix_len=n,
                          interpret=True)
    want = _prefixed_attention(q, k, v, 256, n)
    np.testing.assert_allclose(got, want, atol=2e-5)
    # spelt out for one row: the first ``visible`` prefix rows and the
    # causal part, one softmax
    i = 77
    keys = jnp.concatenate([k[0, 1, :visible], k[0, 1, 256:256 + i + 1]])
    vals = jnp.concatenate([v[0, 1, :visible], v[0, 1, 256:256 + i + 1]])
    p = jax.nn.softmax(keys @ q[0, 1, i] / np.sqrt(128))
    np.testing.assert_allclose(want[0, 1, i], p @ vals, atol=2e-5)
    # the dispatcher off a TPU takes the masked dots
    np.testing.assert_allclose(
        attention(q, k, v, prefix=256, prefix_len=n), want, atol=1e-6)


def test_a_prefix_is_causal_block_aligned_and_alone():
    q = jnp.zeros((1, 1, 128, 128))
    k = jnp.zeros((1, 1, 256, 128))
    with pytest.raises(ValueError):
        flash_attention(q, k, k, causal=True, prefix=100, prefix_len=jnp.int32(0),
                        interpret=True)
    with pytest.raises(ValueError):
        flash_attention(q, k, k, causal=False, prefix=128,
                        prefix_len=jnp.int32(0), interpret=True)
    with pytest.raises(ValueError):
        attention(q, k, k, prefix=128, prefix_len=jnp.int32(0), window=64)
    # without a prefix the kernel and the dispatcher are what they were
    got = flash_attention(q + 1.0, k[:, :, :128] + 1.0, k[:, :, :128] + 2.0,
                          interpret=True)
    np.testing.assert_allclose(got, 2.0, atol=1e-6)
