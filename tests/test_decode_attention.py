"""Ragged decode-attention kernel: interpret-mode equivalence on the CPU.

The kernel's math is checked against ``DecoderLM._cache_attention`` (the
two dots it replaces on a TPU) at small shapes, its write against
``cache_write`` (the scatter it replaces there) bit for bit, and the
dispatching entry is checked to take the scatter and the dots here and
the kernel when lowered for a TPU.
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
    CEILING,
    COVERS,
    cache_attention,
    cache_write,
    decode_attention,
    pack_keys,
    packed_key_rows,
    ragged_decode_attention,
    reads_ragged,
    unpack_keys,
    walk_block,
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


def _rows(k, seed=9):
    """This step's K and V rows for a cache like ``k``: [B, KV, 1, Dh]."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    shape = k.shape[:2] + (1, k.shape[3])
    return (jax.random.normal(ks[0], shape, k.dtype),
            jax.random.normal(ks[1], shape, k.dtype))


def _read(q, k, v, lens, **kw):
    """The kernel as PR 30 had it, a read and nothing else: every lane's
    write is parked at T, where it is dropped."""
    k_new, v_new = _rows(k)
    parked = jnp.full((q.shape[0],), k.shape[2], jnp.int32)
    return ragged_decode_attention(q, k, v, lens, k_new, v_new, parked, **kw)[0]


def _f32(a):
    return np.asarray(a, np.float32)


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
def _assert_step_is_the_scatter_then_the_read(
        q, k, v, lens, wp, tol, block, seed=9):
    """The fused kernel against the two things it replaces: the output is
    bit for bit the read-only kernel's over the cache ``cache_write`` made
    (and the dots' over it to ``tol``); the caches are that cache's where
    ``lens > 0`` and untouched where ``lens == 0``."""
    k_new, v_new = _rows(k, seed)
    got, gk, gv = ragged_decode_attention(
        q, k, v, lens, k_new, v_new, wp, block=block, interpret=True)
    sk = cache_write(k, k_new, wp[:, None])
    sv = cache_write(v, v_new, wp[:, None])
    assert np.array_equal(
        _f32(got), _f32(_read(q, sk, sv, lens, block=block, interpret=True)))
    err = jnp.abs(got.astype(jnp.float32)
                  - _dots(q, sk, sv, lens).astype(jnp.float32))
    assert float(err.max()) <= tol, err.max(axis=(1, 2, 3))
    live = np.asarray(lens) > 0
    for mine, scattered, before in ((gk, sk, k), (gv, sv, v)):
        assert np.array_equal(_f32(mine)[live], _f32(scattered)[live])
        assert np.array_equal(_f32(mine)[~live], _f32(before)[~live])
    return got, gk, gv


# Tolerance. float32: both sides accumulate in float32 and differ only in
# the order of the softmax's sums (online, per block), a few ulps: 1e-5.
# bfloat16 (the served precision): the dots round the normalised weights
# to bfloat16 before the second dot, the kernel rounds the unnormalised
# ones and divides after, and both round the output: two bfloat16 ulps of
# an output below 2, 2 x 2**-7.
@pytest.mark.parametrize("write", ["parked", "step"])
@pytest.mark.parametrize("rep", [1, 2, 4])
@pytest.mark.parametrize("dtype,tol", [("float32", 1e-5), ("bfloat16", 2 ** -6)])
def test_kernel_matches_the_dots(rep, dtype, tol, write):
    """``parked``: the read alone. ``step``: every lane writes where its
    read ends, as a decode step does: ``LENS`` puts that on position 0,
    a block's last row and the next one's first, and on T - 1, beside an
    idle lane that neither reads nor writes."""
    q, k, v = _inputs(rep, jnp.dtype(dtype))
    lens = jnp.asarray(LENS, jnp.int32)
    if write == "step":
        got, _, _ = _assert_step_is_the_scatter_then_the_read(
            q, k, v, lens, lens - 1, tol, BLK)
    else:
        got = _read(q, k, v, lens, block=BLK, interpret=True)
        ref = _dots(q, k, v, lens)
        err = jnp.abs(got.astype(jnp.float32) - ref.astype(jnp.float32))
        assert float(err.max()) <= tol, err.max(axis=(1, 2, 3))
    assert got.shape == q.shape and got.dtype == q.dtype
    # a lane of length 0 reads nothing and gives zeros, exactly
    assert not np.asarray(got[0], np.float32).any()


@pytest.mark.parametrize("write", ["parked", "step"])
@pytest.mark.parametrize("block", [128, 256])
def test_kernel_block_sizes_agree(block, write):
    q, k, v = _inputs(2, jnp.bfloat16, t=512)
    lens = jnp.asarray([0, 1, 255, 256, 257, 512], jnp.int32)
    if write == "step":
        _assert_step_is_the_scatter_then_the_read(
            q, k, v, lens, lens - 1, 2 ** -6, block)
        return
    got = _read(q, k, v, lens, block=block, interpret=True)
    err = jnp.abs(got.astype(jnp.float32)
                  - _dots(q, k, v, lens).astype(jnp.float32))
    assert float(err.max()) <= 2 ** -6


# the cells' block at 4 KV heads of 128 and at 2 of 256 in bfloat16: twice 128
WIDE = 2 * BLOCK


@pytest.mark.parametrize("why,kv,dh,dtype,t,block", [
    # the decode shapes of the benchmark's configurations
    # (benchmark/configs/*.json: KV heads, head size, the cache's dtype,
    # server.max_seq): 128 keys of K and V over 8 KV heads of 128 are 512
    # KiB, a copy that covers the walk's chain
    ("internlm2-1.8b", 8, 128, "bfloat16", 2048, BLOCK),
    ("mistral-7b-v0.3", 8, 128, "bfloat16", 2048, BLOCK),
    # 256 KiB: the block doubles once, and its copy is 512 KiB
    ("trinity-mini", 4, 128, "bfloat16", 4096, WIDE),
    ("qwen3-next-80b-a3b", 2, 256, "bfloat16", 4096, WIDE),
    ("sdar-30b-a3b", 4, 128, "bfloat16", 4096, WIDE),
    # kernels of their own; the batcher's count asks for them all the same
    ("joyai-llm-flash", 32, 64, "bfloat16", 6144, BLOCK),
    ("evabyte", 32, 128, "bfloat16", 16384, BLOCK),
    ("a cache length 256 does not divide", 4, 128, "bfloat16", 4096 - 128, BLOCK),
    ("a float32 cache of 4 KV heads of 128", 4, 128, "float32", 4096, BLOCK),
    ("a float32 cache of 2 KV heads of 128", 2, 128, "float32", 4096, WIDE),
    ("an 8-bit cache of 8 KV heads of 128", 8, 128, "int8", 4096, WIDE),
    ("7 KV heads of 128: 448 KiB", 7, 128, jnp.bfloat16, 2048, WIDE),
    # ONE KV head of 128 in bfloat16 (jamba2-3b): 64 KiB a 128 keys, the block
    # doubles three times, to the ceiling, whose copy is ``COVERS`` itself
    ("jamba2-3b", 1, 128, "bfloat16", 8192, CEILING),
    ("one KV head, a cache that 256 divides and 512 does not", 1, 128,
     "bfloat16", 2304, WIDE),
    ("one KV head, a cache that 512 divides and 1,024 does not", 1, 128,
     "bfloat16", 1536, 512),
    ("a float32 cache of one KV head: half the keys", 1, 128, "float32", 8192,
     CEILING // 2),
    ("2 KV heads of 128: a copy exactly at COVERS stops", 2, 128, "bfloat16",
     8192, 512),
    ("3 KV heads of 128: 512 keys copy 768 KiB, 256 under COVERS", 3, 128,
     "bfloat16", 8192, 512),
    ("rows far under COVERS: the ceiling, no further", 1, 128, "int8", 8192,
     CEILING),
    ("a tiny model's rows: the ceiling where it divides", 2, 8, "float32", 4096,
     CEILING),
])
def test_the_walks_block_is_set_by_the_bytes_a_block_copies(
        why, kv, dh, dtype, t, block):
    """``walk_block``: ONE rule on the call's shapes, for both of the
    kernel's entries, the scheduler's count and the sdar family's: the
    block doubles from 128 while its own copy of K and V is under
    ``COVERS`` and the doubled block divides the cache, to ``CEILING``."""
    assert walk_block(kv, dh, dtype, t) == block, why
    assert t % block == 0 and BLOCK <= block <= CEILING
    copied = 2 * kv * dh * jnp.dtype(dtype).itemsize
    # no block the rule doubled was already a copy that covers the chain
    assert block == BLOCK or copied * (block // 2) < COVERS, why


def test_the_rule_answers_for_the_configurations_as_the_benchmark_holds_them():
    """The same answers from the files themselves, so that a configuration
    whose shapes move is seen to move its kernel's block."""
    import json
    import pathlib

    configs = pathlib.Path(__file__).parent.parent / "benchmark" / "configs"
    got = {}
    for path in sorted(configs.glob("*.json")):
        c = json.loads(path.read_text())
        dh = c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"]
        got[path.stem] = walk_block(
            c["num_key_value_heads"], dh, c["torch_dtype"], c["server"]["max_seq"])
    assert got == {
        "internlm2-1.8b": BLOCK, "mistral-7b-v0.3": BLOCK, "evabyte": BLOCK,
        "joyai-llm-flash": BLOCK, "trinity-mini": WIDE,
        "qwen3-next-80b-a3b": WIDE, "sdar-30b-a3b": WIDE,
        # 8 KV heads of 64 are 4 rows of 128: 256 KiB a 128 keys, as the
        # cache lies (two heads a row) and as the file states it
        "lfm2-24b-a2b": WIDE,
        # ONE KV head of 128: 64 KiB a 128 keys, 512 KiB (``COVERS``) a 1,024:
        # the ceiling (PR 61; PERF.md section 5)
        "jamba2-3b": CEILING,
        # the FULL layers' 4 KV heads, keys of 192 as the file states them:
        # 384 KiB a 128 keys; as the cache holds them (key rows of 256
        # beside values of 128) the same bytes and the same answer
        "mimo-v2.5": WIDE}
    assert CEILING * 1 * (128 + 128) * 2 == COVERS      # jamba's block's copy
    assert walk_block(4, 256, "bfloat16", 12288, 128) == WIDE
    # a ring of 128 rows is one block of the walk, whatever its bytes
    assert walk_block(8, 256, "bfloat16", 128, 128) == BLOCK


# lanes of the one-position call at the rule's block for 2 KV heads of 128 in
# a cache of 1,024 (256 keys in float32, 512 in bfloat16): (length, write
# position). Under 256, on its and on 512's edges, no multiple of either, the
# whole cache; a write in the walk's last block (a step's: ``len - 1``), in
# a block the read does not hold, parked; an idle lane
_WIDE_T = 4 * WIDE
_WIDE_LANES = [
    (0, 70), (1, 0), (72, 71), (255, 254), (256, 255), (257, 256),
    (300, 299), (511, 510), (512, 511), (513, 512), (700, 699),
    (_WIDE_T, _WIDE_T - 1), (640, 900), (72, 600), (530, 3), (333, _WIDE_T),
]


@pytest.mark.parametrize("window", [None, 200, 256, 300, 600])
@pytest.mark.parametrize("dtype,tol,ruled", [
    ("float32", 1e-5, WIDE), ("bfloat16", 2 ** -6, 2 * WIDE)])
def test_the_one_position_kernel_at_the_rules_wide_block(dtype, tol, ruled, window):
    """No ``block=``: the call takes ``walk_block``'s answer for its 2 KV
    heads of 128. With and without ``starts`` (a window whose start lies inside a
    block, on its edge, and before the cache's first position): the output
    is the masked dots' over the cache the scatter made, both caches are
    that cache bit for bit, an idle lane gives zeros and writes nothing."""
    dtype = jnp.dtype(dtype)
    q, k, v = _inputs(4, dtype, t=_WIDE_T, lanes=len(_WIDE_LANES), seed=7)
    assert walk_block(k.shape[1], k.shape[3], k.dtype, _WIDE_T) == ruled
    lens, wp = (jnp.asarray(a, jnp.int32) for a in zip(*_WIDE_LANES))
    starts = None if window is None else jnp.maximum(0, lens - window)
    k_new, v_new = _rows(k)
    got, gk, gv = ragged_decode_attention(
        q, k, v, lens, k_new, v_new, wp, interpret=True, starts=starts)
    live = np.asarray(lens) > 0
    at = jnp.where(lens > 0, wp, _WIDE_T)[:, None]
    sk, sv = cache_write(k, k_new, at), cache_write(v, v_new, at)
    assert np.array_equal(_f32(gk), _f32(sk)) and np.array_equal(_f32(gv), _f32(sv))
    want = cache_attention(q, sk, sv, lens - 1, dtype, lo=starts)
    err = np.abs(_f32(got) - _f32(want))[live]
    assert float(err.max()) <= tol, err.max(axis=(1, 2, 3))
    assert not _f32(got)[~live].any()
    # the same walk under 128 keys a block: the softmax's order alone
    narrow, nk, nv = ragged_decode_attention(
        q, k, v, lens, k_new, v_new, wp, interpret=True, starts=starts,
        block=BLOCK)
    assert np.array_equal(_f32(nk), _f32(gk)) and np.array_equal(_f32(nv), _f32(gv))
    assert float(np.abs(_f32(narrow) - _f32(got)).max()) <= tol


def _one_kv_head_lanes(block):
    """(length, write position) a lane, for a cache of ``2 x CEILING``: an
    idle lane, one key, both sides of 1,024 and of the block, the whole
    cache; every step's write in the last block read (``land``), then a
    write in a block read earlier, in one not read (``land_unread``), and
    a parked one."""
    t = 2 * CEILING
    steps = [1, block - 1, block, block + 1, 1023, 1024, 1025, 2 * block, t]
    return [(0, 70), *((n, n - 1) for n in steps),
            (block + 30, 3), (40, block + 500), (72, t - 1), (333, t)]


@pytest.mark.parametrize("block", [512, CEILING])
def test_one_kv_head_of_128_walks_blocks_of_512_and_of_1024(block):
    """Jamba's call (ONE KV head of 128, 20 query heads, bfloat16) at the
    two blocks the chip chose between, interpreted against the scatter and
    the dots: outputs within one bfloat16 step of the lane's largest,
    caches bit for bit, the idle lane zeros and unwritten; and the rule's
    own answer for this cache is the ceiling."""
    t = 2 * CEILING
    lanes = _one_kv_head_lanes(block)
    q, k, v = _inputs(20, jnp.bfloat16, t=t, lanes=len(lanes), kv=1, seed=61)
    assert walk_block(1, 128, k.dtype, t) == CEILING
    lens, wp = (jnp.asarray(a, jnp.int32) for a in zip(*lanes))
    k_new, v_new = _rows(k)
    got, gk, gv = ragged_decode_attention(
        q, k, v, lens, k_new, v_new, wp, interpret=True,
        block=None if block == CEILING else block)     # None: the rule's own
    live = np.asarray(lens) > 0
    at = jnp.where(lens > 0, wp, t)[:, None]
    sk, sv = cache_write(k, k_new, at), cache_write(v, v_new, at)
    assert np.array_equal(_f32(gk), _f32(sk)) and np.array_equal(_f32(gv), _f32(sv))
    # the unread write landed, the parked one and the idle lane's did not
    assert not np.array_equal(_f32(gk[-3]), _f32(k[-3]))
    assert np.array_equal(_f32(gk[-1]), _f32(k[-1]))
    assert np.array_equal(_f32(gk[0]), _f32(k[0]))
    want = _f32(cache_attention(q, sk, sv, lens - 1, q.dtype))
    err = np.abs(_f32(got) - want).max(axis=(1, 2, 3))
    largest = np.abs(want).max(axis=(1, 2, 3))
    step = 2.0 ** (np.floor(np.log2(largest)) - 7)     # bfloat16: 8 bits
    assert (err[live] <= step[live]).all(), (err, step)
    assert not _f32(got)[~live].any()


@pytest.mark.parametrize("rep", [2, 4])
@pytest.mark.parametrize("why,lens,wp", [
    ("a block's first row", 2 * BLK + 50, 2 * BLK),
    ("a block's last row", 2 * BLK, 2 * BLK - 1),
    ("an odd row inside a group of 8", BLK + 44, BLK + 43),
    ("the cache's last position", T, T - 1),
    ("position 0, read alone", 1, 0),
    ("parked at T: dropped", BLK + 9, T),
    ("parked past T: dropped", BLK + 9, T + 77),
    ("parked below 0: dropped", BLK + 9, -1),
    ("an idle lane at a live position: nothing written", 0, 70),
    ("a block the read has behind it", 2 * BLK + 5, 3),
    ("a block past what the lane reads", BLK + 9, 2 * BLK + 5),
    ("the cache's last position, past the read", 5, T - 1),
])
def test_kernel_lands_the_row_where_the_scatter_would(why, lens, wp, rep):
    """One lane's write position at a time, between two lanes that step
    as usual: what the scatter drops is dropped and the cache untouched,
    what it lands is landed, bit for bit, and the softmax sees the row."""
    q, k, v = _inputs(rep, jnp.bfloat16, lanes=3, seed=len(why))
    lens = jnp.asarray([BLK + 1, lens, 300], jnp.int32)
    wp = jnp.asarray([BLK, wp, 299], jnp.int32)
    _, gk, gv = _assert_step_is_the_scatter_then_the_read(
        q, k, v, lens, wp, 2 ** -6, BLK)
    landed = 0 <= int(wp[1]) < T and int(lens[1]) > 0
    for mine, before in ((gk, k), (gv, v)):
        changed = (_f32(mine)[1] != _f32(before)[1]).any(-1)  # [KV, T]
        assert changed.sum() == (changed.shape[0] if landed else 0), why
        if landed:
            assert changed[:, int(wp[1])].all(), why


@pytest.mark.parametrize("rep", [1, 2, 4])
def test_a_lane_depends_on_its_own_keys_alone(rep):
    """Lane 2's output, and what its write leaves in its cache, are
    bit-equal whatever the other lanes' lengths, whichever lanes are idle
    or parked, and however long the cache (the bucket) is beyond the
    lane's own length."""
    q, k, v = _inputs(rep, jnp.bfloat16)
    k_new, v_new = _rows(k)
    mine = BLK + 37

    def lane_2(k, v, lens, wp):
        o, gk, gv = ragged_decode_attention(
            q, k, v, jnp.asarray(lens, jnp.int32), k_new, v_new,
            jnp.asarray(wp, jnp.int32), block=BLK, interpret=True)
        return (_f32(o[2]), _f32(gk[2, :, :2 * BLK]), _f32(gv[2, :, :2 * BLK]))

    runs = []
    for others in ((0, 0, 0, 0, 0), (T, 1, BLK, 5, 0), (3, T, T, T, T)):
        lens = list(others)
        lens.insert(2, mine)
        # the others step (idle ones write nothing), or are parked
        runs.append(lane_2(k, v, lens, [n - 1 for n in lens]))
        runs.append(lane_2(
            k, v, lens, [mine - 1 if i == 2 else T for i in range(6)]))
    # a shorter cache holding the same keys: the bound moved, the lane not
    runs.append(lane_2(
        k[:, :, :2 * BLK], v[:, :, :2 * BLK], [9, 9, mine, 9, 9, 9],
        [8, 8, mine - 1, 8, 8, 8]))
    for other in runs[1:]:
        for a, b in zip(runs[0], other):
            assert np.array_equal(a, b)
    # and the output depends on the row it wrote: parked, it reads the
    # stale one
    stale = lane_2(k, v, [0, 0, mine, 0, 0, 0], [T] * 6)
    assert not np.array_equal(runs[0][0], stale[0])


def test_lengths_are_clamped_to_the_cache():
    q, k, v = _inputs(2, jnp.bfloat16)
    over = _read(
        q, k, v, jnp.asarray([-3, T + 500, T, 0, 1, 2], jnp.int32),
        block=BLK, interpret=True)
    ref = _read(
        q, k, v, jnp.asarray([0, T, T, 0, 1, 2], jnp.int32),
        block=BLK, interpret=True)
    assert np.array_equal(np.asarray(over, np.float32),
                          np.asarray(ref, np.float32))


def test_kernel_rejects_shapes_it_cannot_tile():
    q, k, v = _inputs(2, jnp.bfloat16, t=BLK + 16)
    with pytest.raises(ValueError, match="do not fit"):
        _read(q, k, v, jnp.zeros((len(LENS),), jnp.int32), block=BLK)


@pytest.mark.parametrize("park", [False, True], ids=["step", "one_parked"])
@pytest.mark.parametrize("attn_len", [None, 2 * BLOCK, 10 * BLOCK])
def test_entry_takes_the_dots_off_tpu(attn_len, park):
    """On this backend the entry is the parent's write and read, byte for
    byte: ``_cache_write``'s scatter of every lane's row (idle lanes'
    too; a parked one dropped), then the dots over the bucket's slice
    under ``key_pos <= pos``, idle lanes (``lens == 0``) computed from
    their stale position like the rest."""
    t = 3 * BLOCK
    q, k, v = _inputs(2, jnp.bfloat16, t=t)
    k_new, v_new = _rows(k)
    bound = t if attn_len is None else min(attn_len, t)
    pos = jnp.asarray([7, 0, BLOCK - 1, BLOCK, bound - 2, bound - 1], jnp.int32)
    lens = jnp.where(jnp.arange(len(LENS)) == 0, 0, pos + 1)
    wp = pos.at[2].set(t) if park else pos
    got, gk, gv = decode_attention(
        q, k, v, k_new, v_new, wp, pos, lens, attn_len=attn_len)
    sk = DecoderLM._cache_write(k, k_new, wp[:, None])
    sv = DecoderLM._cache_write(v, v_new, wp[:, None])
    ref = DecoderLM._cache_attention(
        q, sk[:, :, :bound], sv[:, :, :bound], pos, q.dtype)
    for a, b in ((got, ref), (gk, sk), (gv, sv)):
        assert np.array_equal(_f32(a), _f32(b))
    # the idle lane's row is written here, the parked one's is not
    assert not np.array_equal(_f32(gk[0]), _f32(k[0]))
    assert np.array_equal(_f32(gk[2]), _f32(k[2])) is park
    lowered = jax.jit(
        lambda *a: decode_attention(*a, attn_len=attn_len)
    ).lower(q, k, v, k_new, v_new, wp, pos, lens).as_text()
    assert "tpu_custom_call" not in lowered
    assert lowered.count('"stablehlo.scatter"(') == 2


def _entry_args(**kwargs):
    """``decode_attention``'s arguments for a step: every lane writes
    where its read ends (an idle lane at 0, which the kernel skips)."""
    q, k, v = _inputs(2, jnp.bfloat16, **kwargs)
    lens = jnp.asarray(LENS, jnp.int32)
    pos = jnp.maximum(lens - 1, 0)
    return (q, k, v, *_rows(k), pos, pos, lens)


def test_entry_picks_the_kernel_by_the_platform_it_is_lowered_for():
    """``jax.default_backend()`` is the CPU here; lowered for a TPU the
    same call holds the Mosaic kernel with both caches aliased through
    it, and no dot over the cache nor scatter into it."""
    args = _entry_args(t=2 * BLOCK)
    fn = jax.jit(lambda *a: decode_attention(*a, attn_len=BLOCK))
    assert jax.default_backend() != "tpu"
    mlir = jax.export.export(fn, platforms=["tpu"])(*args).mlir_module()
    assert mlir.count("tpu_custom_call") == 1
    assert "dot_general" not in mlir
    assert "scatter" not in mlir
    assert mlir.count("#stablehlo.output_operand_alias<") == 2


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
    assert mlir.count('"stablehlo.scatter"(') == 2, why


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
    """Heads that tile, lowered for a TPU: the kernel, which leaves an
    idle lane's cache alone. The same call once the rule says no: the
    scatter and the dots, bit for bit, and the rule was asked with what
    the entry can see."""
    mod, entry = on_a_tpu
    args = _entry_args(t=2 * BLOCK)
    q, k, v, k_new, v_new, wp, pos, lens = args
    sk, sv = (DecoderLM._cache_write(c, n, wp[:, None])
              for c, n in ((k, k_new), (v, v_new)))
    dots = DecoderLM._cache_attention(q, sk, sv, pos, q.dtype)
    kernel, kk, kv = entry(*args)
    assert not np.asarray(kernel[0], np.float32).any()  # lens[0] == 0
    assert np.asarray(dots[0], np.float32).any()
    assert np.array_equal(_f32(kk[0]), _f32(k[0]))
    assert np.array_equal(_f32(kk[1:]), _f32(sk[1:]))
    assert np.array_equal(_f32(kv[1:]), _f32(sv[1:]))
    asked = []

    def no(*args):
        asked.append(args)
        return False

    monkeypatch.setattr(mod, "reads_ragged", no)
    got = entry(*args, mesh=None)
    for a, b in zip(got, (dots, sk, sv)):
        assert np.array_equal(_f32(a), _f32(b))
    # (the last two: the values' width, None where it is the keys', and
    # the rows of K's KV axis that hold packed rests)
    assert asked == [("tpu", q.shape, k.shape, (q.dtype,) * 3, None, None, 0)]


@pytest.mark.parametrize("lens", [
    (0, 1, BLOCK - 1, BLOCK, BLOCK + 1, 2 * BLOCK),
    (0, 0, 0, 0, 0, 2 * BLOCK),
    (2 * BLOCK, 0, 2 * BLOCK, 7, 2 * BLOCK, 0),
], ids=["every_edge", "one_lane_at_the_bucket", "full_and_idle"])
def test_kernel_without_a_bucket_is_the_bucketed_one_bit_for_bit(on_a_tpu, lens):
    """What the batcher relies on where the read is ragged: the bucket is
    a clamp on lengths that never exceed it, so ``attn_len=None`` (the
    clamp is the cache's length) gives the bucket's outputs and caches
    exactly, idle lanes and lanes at the bucket itself included."""
    _, entry = on_a_tpu
    q, k, v = _inputs(2, jnp.bfloat16, t=3 * BLOCK, seed=3)
    lens = jnp.asarray(lens, jnp.int32)
    pos = jnp.maximum(lens - 1, 0)
    args = (q, k, v, *_rows(k), pos, pos, lens)
    bucketed = entry(*args, attn_len=2 * BLOCK)
    free = entry(*args, attn_len=None)
    for a, b in zip(free, bucketed):
        assert np.array_equal(_f32(a), _f32(b))
    sk, sv = (DecoderLM._cache_write(c, n, pos[:, None])
              for c, n in zip((k, v), _rows(k)))
    ref = _dots(q, sk, sv, lens)
    err = jnp.abs(free[0].astype(jnp.float32) - ref.astype(jnp.float32))
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
    nothing. The first layer's cache (the same rows on both paths) is the
    scatter's bit for bit on the live lanes, and an idle lane's is left
    as it was."""
    import seldon_core_tpu.ops as ops

    def through_kernel(q, k, v, k_new, v_new, write_pos, pos, lens,
                       attn_len=None, mesh=None):
        assert attn_len == 384 and mesh is None
        return ragged_decode_attention(
            q, k, v, jnp.minimum(lens, attn_len), k_new, v_new, write_pos,
            block=BLOCK, interpret=True)

    model, params = _tiny_model()
    cache_k, cache_v, tokens, pos, active = _step_inputs(model)
    live = np.asarray(active)
    dots, dk, dv = model.decode_step_ragged_list(
        params, cache_k, cache_v, tokens, pos, attn_len=384)
    monkeypatch.setattr(ops, "decode_attention", through_kernel)
    every, ek, _ = model.decode_step_ragged_list(
        params, cache_k, cache_v, tokens, pos, attn_len=384)
    some, sk, sv = model.decode_step_ragged_list(
        params, cache_k, cache_v, tokens, pos, attn_len=384,
        lens=jnp.where(active, pos + 1, 0))
    assert np.array_equal(np.asarray(every)[live], np.asarray(some)[live])
    assert np.array_equal(np.asarray(ek[-1], np.float32)[live],
                          np.asarray(sk[-1], np.float32)[live])
    for mine, scattered, before in ((sk, dk, cache_k), (sv, dv, cache_v)):
        assert np.array_equal(_f32(mine[0])[live], _f32(scattered[0])[live])
        assert not np.array_equal(_f32(mine[0])[live], _f32(before[0])[live])
        for layer, was in zip(mine, before):
            assert np.array_equal(_f32(layer)[~live], _f32(was)[~live])
    spread = float(jnp.std(dots))
    assert float(jnp.abs(dots - some)[live].max()) < 0.05 * spread


@pytest.mark.parametrize("pos,k,bucket,block,want", [
    (0, 1, 128, BLOCK, BLOCK),                       # one key is one block
    (BLOCK - 1, 1, 2 * BLOCK, BLOCK, BLOCK),         # the block's last position
    (BLOCK, 1, 2 * BLOCK, BLOCK, 2 * BLOCK),         # the next block's first
    (BLOCK - 2, 4, 4 * BLOCK, BLOCK, 2 * BLOCK + 2 * 2 * BLOCK),  # crossing inside
    # a lane's length is clamped to the bucket
    (3 * BLOCK, 8, 2 * BLOCK, BLOCK, 8 * 2 * BLOCK),
    # a wider block: whole blocks, past a bucket that 256 does not divide
    (0, 1, 128, WIDE, 256),
    (255, 1, 640, WIDE, 256),
    (256, 1, 640, WIDE, 512),
    (254, 4, 640, WIDE, 2 * 256 + 2 * 512),
    (600, 4, 640, WIDE, 4 * 768),
    (3 * BLOCK, 8, 2 * BLOCK, WIDE, 8 * 2 * BLOCK),
    # the ceiling: one key is a block of 1,024, as 1,023 are
    (0, 1, 128, CEILING, 1024),
    (1022, 4, 2304, CEILING, 2 * 1024 + 2 * 2048),
    (2300, 2, 2304, CEILING, 2 * 3072),
])
def test_positions_streamed_rounds_each_step_to_the_block(pos, k, bucket, block, want):
    from seldon_core_tpu.serving.continuous import _positions_streamed

    assert _positions_streamed(pos, k, bucket, block) == want


@pytest.mark.parametrize("max_seq,block", [
    # 2 KV heads of 8 in float32, rows far under ``COVERS``: the largest
    # block up to the ceiling that divides the cache
    (3 * BLOCK, BLOCK),
    (2 * BLOCK, WIDE),
    (4 * BLOCK, 2 * WIDE),
])
def test_batcher_counts_what_the_read_streams_and_what_the_bucket_held(
        max_seq, block):
    """``kv_positions_read`` / ``kv_positions_bucket`` in ``stats`` (and so
    in a capture's counters): per dispatched burst, what the ragged read
    streams for the lanes active against rows x attn_len x steps; and
    ``kv_rows_written`` / ``kv_rows_written_in_kernel``: the rows the
    burst's live lanes land, and how many of them the kernel lands."""
    from seldon_core_tpu.serving.continuous import (
        ContinuousBatcher,
        _positions_streamed,
    )

    model = DecoderLM(vocab_size=256, d_model=32, n_layers=2, n_heads=4,
                      n_kv_heads=2, d_ff=64, max_seq=max_seq, dtype="float32")
    b = ContinuousBatcher(model, model.init_params(0), slots=4,
                          max_seq=max_seq, prefill_buckets=(8, 16),
                          steps_per_poll=2)
    assert b._kv_read_block == block == walk_block(2, 8, "float32", max_seq)
    b.trace_groups = []
    try:
        assert b.stats["kv_positions_read"] == b.stats["kv_positions_bucket"] == 0
        out = b.submit([3, 17, 42, 99, 7], max_new_tokens=9).result(timeout=120)
        assert len(out) == 5 + 9
        stats, groups = dict(b.stats), list(b.trace_groups)
    finally:
        b.close()
    assert groups and {len(g["lanes"]) for g in groups} == {1}
    # one lane of four, a few positions deep: a block of the kernel's walk a
    # step, whatever the bucket; the dots read the bucket of all four rows
    assert stats["kv_positions_bucket"] == sum(
        2 * 4 * g["attn_len"] for g in groups)
    assert stats["kv_positions_read"] == len(groups) * _positions_streamed(5, 2, 128, block)
    assert stats["kv_positions_read"] == 2 * block * len(groups)
    if block <= WIDE:   # (rows this tiny walk blocks wider than the bucket)
        assert stats["kv_positions_read"] * 2 <= stats["kv_positions_bucket"]
    counters = b.capture_counters()["counters"]
    assert "kv_positions_read" in counters
    # the write: one lane x 2 steps x 2 layers x (K, V) a burst, by the
    # scatter on this CPU, so none of them from inside the kernel
    assert stats["kv_rows_written"] == len(groups) * 1 * 2 * 2 * 2
    assert stats["kv_rows_written_in_kernel"] == 0
    assert counters["kv_rows_written"] == stats["kv_rows_written"]
    assert counters["kv_rows_written_in_kernel"] == 0


# -- keys wider than values, a sink in the softmax, a cache that is a ring ------
# (the mimo_v2 block's calls: ISSUE 57)

def _wide(seed, lanes, kv, rep, t, dk, dv, dtype):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    dt = jnp.dtype(dtype)
    return (jax.random.normal(ks[0], (lanes, kv * rep, 1, dk), dt),
            jax.random.normal(ks[1], (lanes, kv, t, dk), dt),
            jax.random.normal(ks[2], (lanes, kv, t, dv), dt),
            jax.random.normal(ks[3], (lanes, kv, 1, dk), dt),
            jax.random.normal(ks[4], (lanes, kv, 1, dv), dt),
            3.0 + jax.random.normal(ks[5], (kv * rep,), jnp.float32))


@pytest.mark.parametrize("why,kv,rep,t,lens,wp,sink", [
    # 16 queries a KV head over a long cache: the full layers' call
    ("full: 16 a head", 2, 16, 2 * WIDE,
     [0, 1, 255, 256, 257, 2 * WIDE], [9, 0, 254, 255, 256, 511], False),
    # a ring of one block: lanes not yet once round (the bound of the slots
    # written), a full ring written anywhere in it, an idle lane, a parked one
    ("ring: a sink", 2, 4, BLOCK,
     [0, 1, 77, BLOCK, BLOCK, BLOCK, 5], [BLOCK, 0, 76, 127, 0, 63, BLOCK],
     True),
    ("ring: no sink", 2, 4, BLOCK, [3, BLOCK, 0], [2, 40, BLOCK], False),
])
@pytest.mark.parametrize("dtype,tol", [("float32", 1e-5), ("bfloat16", 2 ** -6)])
def test_keys_wider_than_values_a_sink_and_a_ring(why, kv, rep, t, lens, wp,
                                                  sink, dtype, tol):
    """The kernel (interpreted) against the scatter and the dots at keys of
    256 beside values of 128: outputs of the values' width, caches bit for
    bit, a lane of length 0 zeros and unwritten; the sink is one more logit
    a head with no value row, so a row's weights sum to less than one."""
    q, k, v, kn, vn, logits = _wide(7, len(lens), kv, rep, t, 256, 128, dtype)
    if t == 2 * WIDE:   # (what the call walks with no ``block=``)
        assert walk_block(kv, 256, dtype, t, 128) == (
            WIDE if dtype == "float32" else 2 * WIDE)
    lens, wp = jnp.asarray(lens, jnp.int32), jnp.asarray(wp, jnp.int32)
    s = logits if sink else None
    o, k2, v2 = ragged_decode_attention(
        q, k, v, lens, kn, vn, wp, interpret=True, sink=s,
        name="swa_ring_attention" if sink else None)
    assert o.shape == (len(lens), kv * rep, 1, 128)
    live = np.asarray(lens) > 0
    kk = cache_write(k, kn, jnp.where(lens > 0, wp, t)[:, None])
    vv = cache_write(v, vn, jnp.where(lens > 0, wp, t)[:, None])
    want = cache_attention(q, kk, vv, lens - 1, q.dtype, sink=s)
    np.testing.assert_allclose(
        np.asarray(o, np.float32)[live], np.asarray(want, np.float32)[live],
        atol=tol, rtol=tol)
    assert not np.asarray(o, np.float32)[~live].any()
    assert jnp.array_equal(k2, kk) and jnp.array_equal(v2, vv)
    if sink:
        # without the sink the same rows weigh more: it took its share
        bare = cache_attention(q, kk, vv, lens - 1, q.dtype)
        assert float(jnp.abs(bare.astype(jnp.float32)
                             - want.astype(jnp.float32))[live].max()) > 10 * tol


def test_a_sink_takes_one_position_a_lane_and_no_starts():
    q, k, v, kn, vn, s = _wide(1, 2, 1, 2, BLOCK, 128, 128, "float32")
    lens = jnp.asarray([4, 9], jnp.int32)
    with pytest.raises(ValueError, match="a sink"):
        ragged_decode_attention(q, k, v, lens, kn, vn, lens - 1,
                                interpret=True, sink=s, starts=lens * 0)


@pytest.mark.parametrize("why,dk,dv,want", [
    ("keys of 256 beside values of 128", 256, 128, True),
    ("a key row of 192: Mosaic slices none", 192, 128, False),
    ("values that fill no lanes", 256, 64, False),
])
def test_the_rule_for_a_ragged_read_at_two_widths(why, dk, dv, want):
    dts = (jnp.bfloat16,) * 3
    assert reads_ragged("tpu", (64, 64, 1, dk), (64, 4, 12288, dk), dts,
                        None, dv) is want, why
    assert reads_ragged("tpu", (64, 64, 1, dk), (64, 8, BLOCK, dk), dts,
                        None, dv) is want, why
    assert not reads_ragged("cpu", (64, 64, 1, dk), (64, 4, 12288, dk), dts,
                            None, dv)


@pytest.mark.parametrize("sink", [False, True])
def test_entry_takes_the_dots_off_tpu_at_two_widths(sink):
    """``decode_attention()`` on the CPU: the scatter and the dots, the ring
    bounded by ``pos = lens - 1``; a parked lane's row is dropped."""
    q, k, v, kn, vn, logits = _wide(3, 4, 2, 4, BLOCK, 256, 128, "float32")
    lens = jnp.asarray([1, 60, BLOCK, BLOCK], jnp.int32)
    wp = jnp.asarray([0, 59, 17, BLOCK], jnp.int32)
    s = logits if sink else None
    o, k2, v2 = decode_attention(q, k, v, kn, vn, wp, lens - 1, lens, sink=s,
                                 name="swa_ring_attention")
    o3, k3, v3 = ragged_decode_attention(q, k, v, lens, kn, vn, wp,
                                         interpret=True, sink=s)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o3), atol=1e-5)
    assert jnp.array_equal(k2, k3) and jnp.array_equal(v2, v3)
    assert jnp.array_equal(k2[3], k[3])


@pytest.mark.parametrize("dtype,tol", [("float32", 1e-5), ("bfloat16", 2 ** -6)])
def test_one_entry_takes_a_window_at_two_widths(dtype, tol):
    """``decode_attention()`` is ONE choice between the kernel and the dots
    for every caller, so ``starts`` meets keys wider than values there: the
    dots under the band are the kernel's windowed walk (interpreted), caches
    bit for bit; a sink beside ``starts`` is refused before either."""
    t = 2 * WIDE
    q, k, v, kn, vn, logits = _wide(11, 4, 2, 4, t, 256, 128, dtype)
    lens = jnp.asarray([1, 200, 300, t], jnp.int32)
    starts = jnp.maximum(lens - 128, 0)
    o, k2, v2 = decode_attention(q, k, v, kn, vn, lens - 1, lens - 1, lens,
                                 starts=starts)
    assert o.shape == (4, 8, 1, 128)
    want, k3, v3 = ragged_decode_attention(q, k, v, lens, kn, vn, lens - 1,
                                           interpret=True, starts=starts)
    assert jnp.array_equal(k2, k3) and jnp.array_equal(v2, v3)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(want, np.float32), atol=tol, rtol=tol)
    with pytest.raises(ValueError, match="a sink"):
        decode_attention(q, k, v, kn, vn, lens - 1, lens - 1, lens,
                         starts=starts, sink=logits)


# -- keys wider than a row, held cut and packed (the mimo_v2 block's: ISSUE 59) --

def _packed_case(seed, lanes, kv, rep, t, dk, dtype, w=1):
    """A case at keys of ``dk`` beside values of 128: the keys whole, for
    the scatter and the dots, and as the cache holds them packed."""
    q, k, v, kn, vn, logits = _wide(seed, lanes, kv, rep, t, dk, 128, dtype)
    if w > 1:
        ks = jax.random.split(jax.random.PRNGKey(seed + 1), 3)
        dt = jnp.dtype(dtype)
        q = jax.random.normal(ks[0], (lanes, kv * rep, w, dk), dt)
        kn = jax.random.normal(ks[1], (lanes, kv, w, dk), dt)
        vn = jax.random.normal(ks[2], (lanes, kv, w, 128), dt)
    packed = packed_key_rows(dk, kv)
    assert packed
    return (q, k, v, kn, vn, logits, packed, pack_keys(k, packed),
            pack_keys(kn, packed))


@pytest.mark.parametrize("why,kv,rep,t,dk,lens,wp,sink", [
    # lanes on both sides of a block's edge, one of length 0, a parked
    # write, the write landing in the last block of the read and in none
    ("full: two heads' rests a row", 4, 4, 2 * WIDE, 192,
     [0, 1, 255, 256, 257, 2 * WIDE, 300, 40],
     [9, 0, 254, 255, 256, 511, 2 * WIDE, 400], False),
    # a ring of one block under its own name: lanes not yet once round, a
    # full ring written anywhere in it, an idle lane, a parked one
    ("ring: a sink", 4, 2, BLOCK, 192,
     [0, 1, 77, BLOCK, BLOCK, BLOCK, 5], [BLOCK, 0, 76, 127, 0, 63, BLOCK],
     True),
    ("ring: four heads' rests a row", 4, 2, BLOCK, 160,
     [3, BLOCK, 0, 64], [2, 40, BLOCK, 63], True),
])
@pytest.mark.parametrize("dtype,tol", [("float32", 1e-5), ("bfloat16", 2 ** -6)])
def test_packed_key_rows_are_the_read_of_the_keys_held_whole(
        why, kv, rep, t, dk, lens, wp, sink, dtype, tol):
    """The kernel (interpreted) over K ``[B, KV + packed, T, 128]`` against
    ``cache_write`` + ``cache_attention`` over the same keys held whole:
    the scores are the part's product plus the packed row's, scaled by the
    KEY's width; the cache it leaves is the scatter's rows packed, bit for
    bit, and a lane of length 0 gives zeros and writes nothing."""
    q, k, v, kn, vn, logits, packed, rows, new = _packed_case(
        13, len(lens), kv, rep, t, dk, dtype)
    lens, wp = jnp.asarray(lens, jnp.int32), jnp.asarray(wp, jnp.int32)
    s = logits if sink else None
    o, k2, v2 = ragged_decode_attention(
        q[..., :128], rows, v, lens, new, vn, wp, interpret=True, sink=s,
        name="swa_ring_attention" if sink else None, q_rest=q[..., 128:])
    assert o.shape == (len(lens), kv * rep, 1, 128)
    assert k2.shape == (len(lens), kv + packed, t, 128)
    live = np.asarray(lens) > 0
    at = jnp.where(lens > 0, wp, t)[:, None]
    kk, vv = cache_write(k, kn, at), cache_write(v, vn, at)
    want = cache_attention(q, kk, vv, lens - 1, q.dtype, sink=s)
    np.testing.assert_allclose(
        np.asarray(o, np.float32)[live], np.asarray(want, np.float32)[live],
        atol=tol, rtol=tol)
    assert not np.asarray(o, np.float32)[~live].any()
    assert jnp.array_equal(k2, pack_keys(kk, packed))
    assert jnp.array_equal(unpack_keys(k2, packed), kk)
    assert jnp.array_equal(v2, vv)
    # the rest matters: the parts alone are another read
    bare = cache_attention(q[..., :128], kk[..., :128], vv, lens - 1, q.dtype,
                           sink=s)
    assert float(jnp.abs(bare.astype(jnp.float32)
                         - want.astype(jnp.float32))[live].max()) > 10 * tol


def test_a_block_of_positions_over_packed_key_rows():
    """The second entry is the same walk with ``W`` times the query rows a
    KV head, so packed rows meet it as they are: a block of 4 positions a
    lane against the scatter and the dots over the keys held whole."""
    lens = jnp.asarray([4, 260, 0, 512], jnp.int32)
    wp = jnp.asarray([0, 256, 8, 508], jnp.int32)
    q, k, v, kn, vn, _s, packed, rows, new = _packed_case(
        17, 4, 2, 2, 2 * WIDE, 192, "float32", w=4)
    o, k2, v2 = ragged_decode_attention(
        q[..., :128], rows, v, lens, new, vn, wp, interpret=True,
        q_rest=q[..., 128:])
    at = jnp.where(lens[:, None] > 0, wp[:, None] + jnp.arange(4), 512)
    kk, vv = cache_write(k, kn, at), cache_write(v, vn, at)
    want = cache_attention(q, kk, vv, jnp.broadcast_to(
        lens[:, None] - 1, (4, 4)), q.dtype)
    live = np.asarray(lens) > 0
    np.testing.assert_allclose(np.asarray(o)[live], np.asarray(want)[live],
                               atol=1e-5)
    assert jnp.array_equal(k2, pack_keys(kk, packed)) and jnp.array_equal(v2, vv)


@pytest.mark.parametrize("sink", [False, True])
def test_entry_takes_the_dots_off_tpu_over_packed_key_rows(sink):
    """``decode_attention()`` on the CPU: the scatter of the packed row and
    the dots over the keys put back whole are the kernel's read
    (interpreted), caches bit for bit; and the rule answers for the shapes
    as the mimo cell holds them."""
    q, k, v, kn, vn, logits, packed, rows, new = _packed_case(
        3, 4, 4, 2, BLOCK, 192, "float32")
    lens = jnp.asarray([1, 60, BLOCK, BLOCK], jnp.int32)
    wp = jnp.asarray([0, 59, 17, BLOCK], jnp.int32)
    s = logits if sink else None
    o, k2, v2 = decode_attention(
        q[..., :128], rows, v, new, vn, wp, lens - 1, lens, sink=s,
        name="swa_ring_attention", q_rest=q[..., 128:])
    o3, k3, v3 = ragged_decode_attention(
        q[..., :128], rows, v, lens, new, vn, wp, interpret=True, sink=s,
        q_rest=q[..., 128:])
    np.testing.assert_allclose(np.asarray(o), np.asarray(o3), atol=1e-5)
    assert jnp.array_equal(k2, k3) and jnp.array_equal(v2, v3)
    assert jnp.array_equal(k2[3], rows[3])
    dts = (jnp.bfloat16,) * 3
    assert reads_ragged("tpu", (64, 64, 1, 128), (64, 6, 12288, 128), dts,
                        None, 128, 2)
    assert reads_ragged("tpu", (64, 64, 1, 128), (64, 12, BLOCK, 128), dts,
                        None, 128, 4)
    # 64 query heads do not group over 6 rows: the packed ones are not heads
    assert not reads_ragged("tpu", (64, 64, 1, 128), (64, 6, 12288, 128), dts,
                            None, 128)


def test_packed_rows_and_the_queries_rests_come_together():
    q, k, v, kn, vn, _s, packed, rows, new = _packed_case(
        5, 2, 4, 2, BLOCK, 192, "float32")
    lens = jnp.asarray([4, 9], jnp.int32)
    with pytest.raises(ValueError, match="packed rows"):
        ragged_decode_attention(q[..., :128], rows, v, lens, new, vn, lens - 1,
                                interpret=True)
    with pytest.raises(ValueError, match="packed rows"):
        ragged_decode_attention(q[..., :128], k[..., :128], v, lens,
                                kn[..., :128], vn, lens - 1, interpret=True,
                                q_rest=q[..., 128:])
    with pytest.raises(ValueError, match="packed rows"):
        ragged_decode_attention(q[..., :128], rows, v, lens, new, vn, lens - 1,
                                interpret=True, q_rest=q[..., 128:160])
