"""Attention that is open inside a block (generation by blocks): the ragged
decode kernel's second entry, a block of W positions a lane
(``ops/decode_attention.py``), interpreted on the CPU against the scatter
and the dots; and the flash kernel's block mask
(``ops/flash_attention.py``) against the masked dots, and those against
the mask written out."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from seldon_core_tpu.ops import attention
from seldon_core_tpu.ops.decode_attention import (
    BLOCK, GROUP, block_decode_attention, block_reads_ragged, cache_attention,
    cache_write, ragged_decode_attention, walk_block)
from seldon_core_tpu.ops.flash_attention import (
    _block_attention, _xla_attention, flash_attention)

B, KV, REP, DH, T = 7, 2, 3, 128, 512


def _arrays(w, seed=0, dtype=jnp.float32, lanes=B, rep=REP, t=T, kv=KV):
    rng = np.random.default_rng(seed)
    draw = lambda *s: jnp.asarray(rng.standard_normal(s), dtype)  # noqa: E731
    return (draw(lanes, kv * rep, w, DH), draw(lanes, kv, t, DH),
            draw(lanes, kv, t, DH), draw(lanes, kv, w, DH), draw(lanes, kv, w, DH))


F32, BF16 = jnp.float32, jnp.bfloat16
# (positions a lane, heads a KV head, cache length, dtype, block: None is
# the rule's own, ``walk_block``, KV heads of 128); the block the rule gives
WALKS = [
    # 2 KV heads of 128: 128 keys of K and V are 256 KiB in float32, so the
    # rule's block is 256, and 128 KiB in bfloat16, where it doubles once
    # more (512 keys copy 512 KiB) in a cache that 512 divides; whatever the
    # query rows a KV head brings (6 to 24 here); under 128 too
    *[((w, REP, T, dtype, block, KV), 256 if dtype is F32 else 512)
      for w in (2, 4, 8)
      for dtype in (F32, BF16) for block in (None, BLOCK)],
    # the block pass's 32 rows (8 heads x 4 positions, 4 x 8); under 512
    # (which at the sdar cell's 4 KV heads the chip read no faster) and 128
    *[((4, 8, 1024, dtype, block, KV), 256 if dtype is F32 else 512)
      for dtype in (F32, BF16) for block in (None, 512, BLOCK)],
    ((8, 4, 1024, BF16, None, KV), 512),
    # a cache that 256 divides and 512 does not
    ((4, 8, 768, BF16, None, KV), 256),
    # a cache that only 128 divides
    ((4, 8, 640, BF16, None, KV), BLOCK),
    # 128 keys of 512 KiB (8 KV heads in bfloat16, 4 in float32): their
    # copy covers the chain, the rule's block is 128
    ((4, 2, 512, BF16, None, 8), BLOCK),
    ((4, 2, 512, F32, None, 4), BLOCK),
]


@pytest.mark.parametrize(
    "walk,ruled", WALKS,
    ids=[f"w{w}-rep{r * w}-t{t}-{np.dtype(d).name}-{b or 'rule'}-kv{kv}"
         for (w, r, t, d, b, kv), _ in WALKS])
def test_the_block_kernel_is_the_scatter_and_the_dots(walk, ruled):
    """Lanes whose blocks end at an edge of 128, 256 and 512 keys and start
    at it, in the first and in the last group of the cache, an idle lane and
    one that writes into a block its read does not hold among them, the
    write of every other in its walk's last block: the kernel's output and
    both caches are the scatter's and the dots' (the caches bit for bit),
    the idle lane's rows are left alone, under the block the rule gives the
    call's shapes and under 128."""
    w, rep, t, dtype, block, kv = walk
    assert walk_block(kv, DH, dtype, t) == ruled
    edges = [e for e in (128, 256, 512) if e < t]
    base = np.array([0, *(e - w for e in edges), *edges, 0,
                     2 * BLOCK + 3 * w, t - w, 40 // w * w, t - 2 * w])
    lanes, idle, unread = len(base), len(edges) * 2 + 1, len(base) - 1
    lens = base + w
    lens[idle], lens[unread] = 0, 72
    q, k, v, k_new, v_new = _arrays(w, w, dtype, lanes, rep, t, kv)
    got = ragged_decode_attention(
        q, k, v, jnp.asarray(lens), k_new, v_new, jnp.asarray(base),
        block=block, interpret=True)
    live = lens > 0
    # the rows land where the scatter puts them, and nowhere else
    at = jnp.asarray(np.where(live[:, None], base[:, None] + np.arange(w), t))
    want_k, want_v = cache_write(k, k_new, at), cache_write(v, v_new, at)
    want = cache_attention(
        q, want_k, want_v,
        jnp.broadcast_to(jnp.asarray(lens - 1)[:, None], (lanes, w)), dtype)
    tol = 2e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(got[0], np.float32)[live], np.asarray(want, np.float32)[live],
        atol=tol)
    for mine, theirs, before in zip(got[1:], (want_k, want_v), (k, v)):
        np.testing.assert_array_equal(np.asarray(mine), np.asarray(theirs))
        np.testing.assert_array_equal(np.asarray(mine)[idle], np.asarray(before)[idle])
    # the public entry (here the scatter and the dots) is that reference
    # where a lane reads up to its block's end, as every caller's does
    entry = block_decode_attention(
        q, k, v, k_new, v_new, jnp.asarray(base), jnp.asarray(lens))
    ends = live & (np.arange(lanes) != unread)
    np.testing.assert_allclose(
        np.asarray(entry[0], np.float32)[ends], np.asarray(want, np.float32)[ends],
        atol=tol)
    np.testing.assert_array_equal(np.asarray(entry[1]), np.asarray(want_k))


@pytest.mark.parametrize("kv,dh,dtype,t,block", [
    # the block pass of the sdar cell: 4 KV heads of 128 in bfloat16 (128
    # keys of K and V: 256 KiB), whatever the cache that 256 divides
    (4, 128, BF16, 4096, 256), (4, 128, BF16, 2048, 256), (4, 128, BF16, 768, 256),
    # fewer bytes still: the block doubles until its copy is 512 KiB, in a
    # cache the doubled block divides
    (2, 128, BF16, 4096, 512), (1, 128, BF16, 256, 256), (2, 256, BF16, 4096, 256),
    (7, 128, BF16, 1024, 256), (2, 128, F32, 1024, 256),
    (1, 128, BF16, 4096, 1024), (2, 128, BF16, 768, 256),
    # 128 keys of 512 KiB or more: the copy covers the chain
    (8, 128, BF16, 4096, BLOCK), (4, 256, BF16, 4096, BLOCK),
    (4, 128, F32, 4096, BLOCK), (16, 128, BF16, 2048, BLOCK),
    # a cache 256 does not divide
    (4, 128, BF16, 640, BLOCK), (4, 128, BF16, 128, BLOCK), (4, 128, BF16, 1152, BLOCK),
])
def test_the_walks_block_follows_the_bytes_a_block_copies(kv, dh, dtype, t, block):
    assert walk_block(kv, dh, dtype, t) == block
    assert t % block == 0


def test_every_query_of_a_block_sees_the_whole_block_and_nothing_after():
    """Against the mask written out: query i of a block at ``base`` sees
    keys [0, base + W), its own block's later positions among them."""
    w = 4
    q, k, v, k_new, v_new = _arrays(w, 9)
    base = jnp.asarray([0, 124, 128, 4, 300, 508, 40])
    lens = base + w
    o, k2, v2 = block_decode_attention(q, k, v, k_new, v_new, base, lens)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, jnp.repeat(k2, REP, axis=1)) / np.sqrt(DH)
    seen = jnp.arange(T)[None, None, None, :] < lens[:, None, None, None]
    p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
    want = jnp.einsum("bhqk,bhkd->bhqd", p, jnp.repeat(v2, REP, axis=1))
    np.testing.assert_allclose(np.asarray(o), np.asarray(want), atol=2e-5)


def test_the_kernel_takes_a_block_that_lies_in_one_group():
    shapes = ((4, 32, 4, 128), (4, 4, 4096, 128))
    dts = (jnp.bfloat16,) * 3
    assert block_reads_ragged("tpu", *shapes, dts)
    assert not block_reads_ragged("cpu", *shapes, dts)
    assert not block_reads_ragged("tpu", (4, 32, 3, 128), shapes[1], dts)
    assert not block_reads_ragged("tpu", (4, 32, 16, 128), shapes[1], dts)
    assert not block_reads_ragged("tpu", *shapes, dts, mesh=object())
    assert GROUP % 4 == 0
    q, k, v, k_new, v_new = _arrays(3)
    with pytest.raises(ValueError, match="do not fit the kernel"):
        ragged_decode_attention(q, k, v, jnp.zeros((B,), jnp.int32), k_new,
                                v_new, jnp.zeros((B,), jnp.int32), interpret=True)
    q, k, v, k_new, v_new = _arrays(4)
    with pytest.raises(ValueError, match="no starts"):
        ragged_decode_attention(
            q, k, v, jnp.zeros((B,), jnp.int32), k_new, v_new,
            jnp.zeros((B,), jnp.int32), interpret=True,
            starts=jnp.zeros((B,), jnp.int32))


@pytest.mark.parametrize("t,block_q,block_k,block", [
    (256, 128, 128, 4), (512, 256, 512, 4), (384, 128, 256, 4),
    (256, 128, 128, 8), (256, 128, 256, 2), (512, 128, 128, 1)])
def test_the_flash_kernels_block_mask_is_the_masked_dots(t, block_q, block_k, block):
    rng = np.random.default_rng(t + block)
    q, k, v = (jnp.asarray(rng.standard_normal((1, 2, t, 128)), jnp.float32)
               for _ in range(3))
    got = flash_attention(q, k, v, block_q=block_q, block_k=block_k,
                          interpret=True, block=block)
    want = _xla_attention(q, k, v, True, block=block)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-5)
    if block == 1:
        # blocks of one position: the causal mask
        np.testing.assert_allclose(
            np.asarray(want), np.asarray(_xla_attention(q, k, v, True)), atol=3e-5)
    else:
        assert np.abs(np.asarray(want) - np.asarray(
            _xla_attention(q, k, v, True))).max() > 0.1


def test_the_masked_dots_are_the_mask_written_out():
    rng = np.random.default_rng(3)
    q, k, v = (jnp.asarray(rng.standard_normal((2, 2, 24, 16)), jnp.float32)
               for _ in range(3))
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / 4.0
    i, j = np.arange(24)[:, None], np.arange(24)[None, :]
    seen = j < (i // 4 + 1) * 4
    want = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(
        jnp.where(seen[None, None], s, -jnp.inf), -1), v)
    np.testing.assert_allclose(np.asarray(_block_attention(q, k, v, 4)),
                               np.asarray(want), atol=1e-5)
    # the dispatching entry takes the same argument on the CPU
    np.testing.assert_allclose(np.asarray(attention(q, k, v, block=4)),
                               np.asarray(want), atol=1e-5)


def test_a_block_is_causal_a_power_of_two_and_takes_no_window():
    q = jnp.zeros((1, 1, 128, 128))
    for how in (dict(block=3), dict(block=256), dict(block=4, window=64),
                dict(block=4, causal=False)):
        with pytest.raises(ValueError):
            flash_attention(q, q, q, interpret=True, **how)
    with pytest.raises(ValueError):
        _xla_attention(q, q, q, True, window=64, block=4)
