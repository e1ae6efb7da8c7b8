"""Single-position decode attention over a LATENT cache (multi-head latent
attention, absorbed form), the step's write included.

A latent layer caches ONE row a position for all heads: the normed
compressed key-value ``c`` (``rank`` wide) beside the one rotary key ``k_r``
every head shares, padded with zeros to a whole number of 128 lanes
(``row_width(rank, rope)``: 512 + 64 -> 640). The decode step never
expands it: the up-projection of the keys is absorbed into the query and
that of the values into the output, so a lane's ``H`` absorbed queries
``[q_n W_UK^T | q_r | 0]`` meet the row itself, once as a key (all of it)
and once as a value (its first ``rank`` lanes):

    s = (q' . row) * scale;  p = softmax(s over the lane's positions)
    o = p @ row[:, :rank]                                [H, rank]

``reads_ragged`` of ``ops/decode_attention.py`` wants a head size that
fills lanes and copies K and V blocks per KV head; here there is one array
to copy and one "KV head" of 640, read twice from VMEM. The kernel is that
one's walk with one buffer: ONE program a layer, a loop over the lanes
with ``lens > 0`` and inside it over the lane's ``ceil(len /
LATENT_BLOCK)`` blocks of ``[LATENT_BLOCK, W]`` (640 KB), double-buffered
across lane boundaries; the block that holds ``write_pos`` takes the
step's new row before it is read and the row's aligned group of ``GROUP`` positions goes
back to the cache, which is aliased in and out of the call. A lane of
length 0 copies nothing, gives zeros and writes nothing.

``latent_decode_attention()`` is the public entry (the kernel where
``latent_reads_ragged`` holds for the platform the executable is lowered
for, the scatter and two dots elsewhere: the same arithmetic in
``jax.numpy``), under ``jax.named_scope("latent_decode_attention")``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .decode_attention import GROUP, NEG_INF

LANES = 128
# Positions of one copy and of the two products that follow it. A lane
# brings only its H query rows to the matrix unit, so a block's time is the
# loop's chain (wait, product, max, exp, sum, product), not its bytes: on a
# v5e a step's 12 layers of 64 lanes at ~2.5k positions take 9.0 ms in
# blocks of 128, 6.3 in 256 and 5.1 in 512, though a lane then streams up
# to 511 positions past its length (PERF.md, PR 42).
LATENT_BLOCK = 512


def row_width(rank: int, rope: int) -> int:
    """Lanes of one cached position: ``rank + rope`` rounded up to whole
    registers (an array's last axis is tiled by 128 in HBM whatever it is
    declared as: the padding is there either way, and a row that says so
    copies and multiplies in whole tiles)."""
    return -(-(rank + rope) // LANES) * LANES


def latent_reads_ragged(platform, q_shape, cache_shape, dtypes, rank,
                        mesh=None) -> bool:
    """Whether ``latent_decode_attention()``, lowered for ``platform``,
    reads each lane's own length (the kernel). ``q_shape`` [B, H, W],
    ``cache_shape`` [B, T, W] of one layer, ``dtypes`` of q and the
    cache. The kernel wants rows and values of whole registers, whole
    blocks and one dtype; a serving mesh takes the dots."""
    return (
        platform == "tpu"
        and mesh is None
        and q_shape[-1] == cache_shape[-1]
        and cache_shape[-1] % LANES == 0
        and rank % LANES == 0
        and cache_shape[1] % LATENT_BLOCK == 0
        and len(set(dtypes)) == 1
    )


def latent_cache_write(cache, new, positions):
    """``new`` [B, W] lands in ``cache`` [B, T, W] at ``positions`` [B]; a
    position outside [0, T) is dropped (``ops.decode_attention.cache_write``
    for a cache without a head axis)."""
    b = new.shape[0]
    index = jnp.stack(
        [jnp.arange(b, dtype=jnp.int32), positions.astype(jnp.int32)], axis=-1)
    return lax.scatter(
        cache, index, new.astype(cache.dtype),
        lax.ScatterDimensionNumbers(
            update_window_dims=(1,), inserted_window_dims=(0, 1),
            scatter_dims_to_operand_dims=(0, 1)),
        mode=lax.GatherScatterMode.FILL_OR_DROP,
    )


def latent_cache_attention(q, cache, bound, rank: int, scale: float):
    """q [B, H, W] over ``cache`` [B, Ta, W] (sliced to what is read)
    under the mask ``key_pos <= bound`` [B]: [B, H, rank] in q's dtype.
    Scores and sums in float32; the cache is never cast or copied."""
    key_pos = jnp.arange(cache.shape[1], dtype=jnp.int32)
    s = jnp.einsum("bhw,btw->bht", q, cache,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(key_pos[None, None, :] <= bound[:, None, None], s, NEG_INF)
    w = jax.nn.softmax(s, -1).astype(q.dtype)
    return jnp.einsum("bht,btc->bhc", w, cache[..., :rank],
                      preferred_element_type=jnp.float32).astype(q.dtype)


def _latent_kernel(lens_ref, wpos_ref, q_ref, new_ref, _c_in, o_ref, c_hbm,
                   buf, stage, sem, wsem, rsem, *, block, rank, scale):
    """The whole batch of one layer (``ops.decode_attention._ragged_kernel``
    with one cache array and no head axis).

    lens_ref, wpos_ref: SMEM [B]; q_ref: VMEM [B, H, W]; new_ref: VMEM [B,
    1, W]; o_ref: VMEM [B, H, rank]; c_hbm: the layer's cache [B, T, W],
    left where it is (the call's aliased output; ``_c_in`` is the same
    buffer); buf: VMEM [2, block, W]; stage: VMEM [B, GROUP, W], a lane's
    patched group while its copy to the cache is in flight; sem [2] the
    reads', wsem [1] the writes', rsem [1] the fetch of a group that no
    block held.
    """
    n_lanes, n_heads, _w = q_ref.shape
    t = c_hbm.shape[1]

    def copy(lane, i, slot):
        start = pl.multiple_of(i * block, block)
        return pltpu.make_async_copy(
            c_hbm.at[lane, pl.ds(start, block), :], buf.at[slot], sem.at[slot])

    def write_back(lane, wp):
        group = pl.multiple_of(wp // GROUP * GROUP, GROUP)
        return pltpu.make_async_copy(
            stage.at[lane], c_hbm.at[lane, pl.ds(group, GROUP), :], wsem.at[0])

    def patched(lane, wp, group):
        """``group`` [GROUP, W] with the lane's new row at ``wp``."""
        row = lax.broadcasted_iota(jnp.int32, group.shape, 0)
        return jnp.where(row == wp % GROUP, new_ref[lane], group)

    def land(lane, wp, slot):
        """The block in ``slot`` holds position ``wp`` and its copy is
        done: the new row replaces the stale one there, and its group
        starts back to the cache (nobody waits for it before the end)."""
        at = pl.ds(pl.multiple_of(wp % block // GROUP * GROUP, GROUP), GROUP)
        group = patched(lane, wp, buf[slot, at, :])
        buf[slot, at, :] = group
        stage[lane] = group
        write_back(lane, wp).start()

    def land_unread(lane, wp):
        """No block of the lane's read held ``wp``: its group comes from
        the cache into the staging buffer, takes the row and goes back."""
        group = pl.multiple_of(wp // GROUP * GROUP, GROUP)
        fetch = pltpu.make_async_copy(
            c_hbm.at[lane, pl.ds(group, GROUP), :], stage.at[lane], rsem.at[0])
        fetch.start()
        fetch.wait()
        stage[lane] = patched(lane, wp, stage[lane])
        write_back(lane, wp).start()

    def next_live(lane):
        """The first lane after ``lane`` that reads anything, or B."""
        return lax.while_loop(
            lambda b: (b < n_lanes)
            & (lens_ref[jnp.minimum(b, n_lanes - 1)] <= 0),
            lambda b: b + 1, lane + 1)

    first = next_live(jnp.int32(-1))

    @pl.when(first < n_lanes)
    def _():
        copy(first, 0, 0).start()

    def lane_body(lane, carry):
        done_blocks, written = carry
        n = lens_ref[lane]
        n_blocks = (n + block - 1) // block
        wp = wpos_ref[lane]
        writes = (n > 0) & (wp >= 0) & (wp < t)
        w_block = jnp.where(writes & (wp // block < n_blocks),
                            wp // block, -1)
        q = q_ref[lane]  # [H, W]

        def block_body(i, carry):
            o, m, l = carry
            slot = (done_blocks + i) % 2

            # the copy after this one: this lane's next block, or the
            # next live lane's first (so a lane boundary costs no wait)
            @pl.when(i + 1 < n_blocks)
            def _():
                copy(lane, i + 1, 1 - slot).start()

            @pl.when(i + 1 == n_blocks)
            def _():
                nxt = next_live(lane)

                @pl.when(nxt < n_lanes)
                def _():
                    copy(nxt, 0, 1 - slot).start()

            copy(lane, i, slot).wait()

            @pl.when(i == w_block)
            def _():
                land(lane, wp, slot)

            rows = buf[slot]  # [block, W]: the keys, and the values' source
            s = lax.dot_general(
                q, rows, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # [H, block]
            col = i * block + lax.broadcasted_iota(jnp.int32, s.shape, 1)
            # the lane's first position is live in its first block, so m is
            # finite from there on and a masked entry's exp underflows to 0
            s = jnp.where(col < n, s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new)
            l = l * alpha + p.sum(axis=-1, keepdims=True)
            o = o * alpha + lax.dot_general(
                p.astype(rows.dtype), rows[:, :rank],
                (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
            return o, m_new, l

        o, _, l = lax.fori_loop(
            0, n_blocks, block_body,
            (jnp.zeros((n_heads, rank), jnp.float32),
             jnp.full((n_heads, 1), NEG_INF, jnp.float32),
             jnp.zeros((n_heads, 1), jnp.float32)),
        )
        # a lane of length 0 ran no block: o = 0, l = 0, zeros out
        o_ref[lane] = (o / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)

        @pl.when(writes & (w_block < 0))
        def _():
            land_unread(lane, wp)

        return done_blocks + n_blocks, written + writes.astype(jnp.int32)

    _, written = lax.fori_loop(
        0, n_lanes, lane_body, (jnp.int32(0), jnp.int32(0)))

    # the next layer-step of this cache is a later call: every write has
    # landed when this one returns (each wait takes one group's bytes)
    def drain(_, carry):
        write_back(0, 0).wait()
        return carry

    lax.fori_loop(0, written, drain, 0)


@functools.partial(jax.jit,
                   static_argnames=("rank", "scale", "block", "interpret"))
def ragged_latent_attention(q, cache, lens, new, write_pos, *, rank: int,
                            scale: float, block: int = LATENT_BLOCK,
                            interpret: bool = False):
    """The Pallas kernel. q [B, H, W] absorbed queries; ``cache`` [B, T,
    W] the layer's latent rows, unsliced (``T`` a multiple of ``block``);
    lens [B] int32, clamped to [0, T]; ``new`` [B, W] this step's rows and
    write_pos [B] where they go. Returns ``(o [B, H, rank], cache)``: the
    cache is aliased in and out, so under a caller that donates it nothing
    but the rows' groups moves.

    Lane b first takes its new row at ``write_pos[b]``, then attends to
    positions [0, lens[b]): the read of the cache ``latent_cache_write()``
    would have made, bit for bit; a ``write_pos`` outside [0, T) is
    dropped, and a lane with ``lens[b] == 0`` reads nothing, gives zeros
    and WRITES NOTHING (``ops.decode_attention.ragged_decode_attention``'s
    contract)."""
    b, h, w = q.shape
    t = cache.shape[1]
    if cache.shape[2] != w or w % LANES or rank % LANES or rank > w \
            or t % block or block % GROUP:
        raise ValueError(
            f"q {q.shape} / cache {cache.shape} / rank {rank} do not fit the "
            f"kernel (one row width, rows and rank multiples of {LANES}, "
            f"cache length a multiple of {block})")
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    out, cache = pl.pallas_call(
        functools.partial(_latent_kernel, block=block, rank=rank,
                          scale=float(scale)),
        out_shape=(
            jax.ShapeDtypeStruct((b, h, rank), q.dtype),
            jax.ShapeDtypeStruct(cache.shape, cache.dtype),
        ),
        in_specs=[smem, smem, vmem, vmem, hbm],
        out_specs=(vmem, hbm),
        input_output_aliases={4: 1},
        scratch_shapes=[
            pltpu.VMEM((2, block, w), cache.dtype),
            pltpu.VMEM((b, GROUP, w), cache.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((1,)),
            pltpu.SemaphoreType.DMA((1,)),
        ],
        interpret=interpret,
        name="latent_decode_attention",
    )(jnp.clip(lens.astype(jnp.int32), 0, t), write_pos.astype(jnp.int32),
      q, new.astype(cache.dtype)[:, None, :], cache)
    return out, cache


@functools.partial(jax.jit,
                   static_argnames=("rank", "scale", "attn_len", "mesh"))
def latent_decode_attention(q, cache, new, write_pos, pos, lens, *,
                            rank: int, scale: float, attn_len=None,
                            mesh=None):
    """The decode step's write and read of one latent layer's cache: this
    step's rows ``new`` [B, W] go into the UNSLICED ``cache`` [B, T, W] at
    ``write_pos`` [B] (outside [0, T): dropped), then the absorbed queries
    q [B, H, W] attend to it. Returns ``(o [B, H, rank], cache)``. ``pos``,
    ``lens``, ``attn_len``, ``mesh``: as ``ops.decode_attention.
    decode_attention`` takes them, and with its contract: the kernel skips
    a lane of ``lens == 0`` (zeros out, no write), the scatter and the
    dots write every lane's row and read ``attn_len`` positions of every
    lane under ``key_pos <= pos``; where ``lens > 0`` the two agree to
    rounding, the cache bit for bit (tests/test_latent_attention.py).

    Jitted, so the burst's unrolled layers lower it once and call it."""
    t = cache.shape[1]
    bound = t if attn_len is None else min(int(attn_len), t)

    def dots(q, cache, new, write_pos, pos, lens):
        cache = latent_cache_write(cache, new, write_pos)
        o = latent_cache_attention(
            q, lax.slice_in_dim(cache, 0, bound, axis=1), pos, rank, scale)
        return o, cache

    def kernel(q, cache, new, write_pos, pos, lens):
        return ragged_latent_attention(
            q, cache, jnp.minimum(lens, bound), new, write_pos, rank=rank,
            scale=scale, block=LATENT_BLOCK)

    args = (q, cache, new, write_pos, pos, lens)
    with jax.named_scope("latent_decode_attention"):
        # the platform is known only when this is lowered: ask whether a
        # lowering for a TPU takes the kernel, and let that lowering choose
        if not latent_reads_ragged(
                "tpu", q.shape, cache.shape, (q.dtype, cache.dtype), rank,
                mesh):
            return dots(*args)
        return lax.platform_dependent(*args, tpu=kernel, default=dots)
