"""Single-position decode attention over an EVA cache, the step's write
included: a lane's WINDOW RING and its SUMMARY ROWS under one softmax.

EVA attention reads a position's own aligned window exactly and every
earlier window as one pooled row a chunk. A layer's cache is therefore two
kinds of two lengths, K and V of each:

    ring       [B, H, W, Dh]        row ``t mod W`` of the lane's current
                                    window (a new window starts over at row
                                    0; the old rows are residue)
    summaries  [B, H, T / C, Dh]    row ``t // C``: chunk c's pooled key and
                                    pooled value, visible from the window
                                    after its own

and a lane at position ``t`` (window ``w = t // W``) attends to ``(t mod W)
+ 1`` ring rows and ``(W / C) w`` summary rows:

    s = scale * [q . ring_k | q . sum_k];   p = softmax(s)   ONE softmax
    o = p_ring @ ring_v + p_sum @ sum_v

``ragged_decode_attention`` of ``ops/decode_attention.py`` reads one array
under one softmax and cannot join two. This kernel is that one's walk over
two: ONE program a layer, a loop over the lanes that read anything and
inside it over the lane's ``ceil(ring rows / block)`` ring blocks and then
its ``ceil(summary rows / block)`` summary blocks, each ``[H, block, Dh]``
of K and of V, double-buffered across kinds and lane boundaries; the ring
block that holds the step's row takes it before it is read and the row's
CHUNK (its aligned group of ``chunk`` positions) goes back to the ring,
which is aliased in and out of the call, and out of the call beside it: the
step that completes a chunk pools it from there (``chunk_summary`` and a
scatter, in the model's step; a gather of the chunk from the ring would
have the compiler carry the ring through the burst in another layout, a
ring-sized copy a layer and step). The summaries are only read here. A lane that reads nothing copies nothing,
gives zeros and writes nothing.

``eva_decode_attention()`` is the public entry (the kernel where
``eva_reads_ragged`` holds for the platform the executable is lowered for,
the scatter and four dots elsewhere: the same arithmetic in ``jax.numpy``),
under ``jax.named_scope("eva_decode_attention")``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .decode_attention import GROUP, NEG_INF, cache_write

# Positions of one copy: K and V blocks of [H, EVA_BLOCK, Dh] each (1 MB
# each at 32 heads of 128 in bfloat16). A window's summaries are W / C =
# 128 rows, so at 128 the summary kind streams nothing past what is live
# and only the ring's last block rounds up.
EVA_BLOCK = 128


def eva_reads_ragged(platform, q_shape, ring_shape, summary_shape, dtypes,
                     chunk: int, mesh=None) -> bool:
    """Whether ``eva_decode_attention()``, lowered for ``platform``, reads
    each lane's own rows of both kinds (the kernel). ``q_shape`` [B, H,
    Dh]; ``ring_shape`` [B, H, W, Dh] and ``summary_shape`` [B, H, Ns, Dh]
    of one layer; ``dtypes`` of q and the four arrays. The kernel wants a
    head size that fills lanes, whole blocks of both kinds, chunks of
    whole groups of ``GROUP`` rows and one dtype; a serving mesh takes the
    dots."""
    return (
        platform == "tpu"
        and mesh is None
        and q_shape[-1] % 128 == 0
        and q_shape[1] == ring_shape[1] == summary_shape[1]
        and ring_shape[2] % EVA_BLOCK == 0
        and summary_shape[2] % EVA_BLOCK == 0
        and chunk % GROUP == 0 and EVA_BLOCK % chunk == 0
        and len(set(dtypes)) == 1
    )


def chunk_summary(k, v, mu, phi, scale: float):
    """The pooled key and pooled value of whole chunks: k, v [..., H, n, C,
    Dh] (keys rotated), ``mu`` and ``phi`` [H, Dh] ->

        k~ = sum_j softmax_j(scale * k_j . mu) k_j
        v~ = sum_j softmax_j(scale * k_j . phi) v_j          j in the chunk

    [..., H, n, Dh] each, in k's dtype; logits, softmax and sums float32."""
    with jax.named_scope("eva_chunk_summary"):
        k32 = k.astype(jnp.float32)
        pooled = []
        for w, rows in ((mu, k32), (phi, v.astype(jnp.float32))):
            logits = jnp.einsum("...hncd,hd->...hnc", k32,
                                w.astype(jnp.float32)) * scale
            p = jax.nn.softmax(logits, axis=-1)
            pooled.append(jnp.einsum("...hnc,...hncd->...hnd", p, rows)
                          .astype(k.dtype))
        return pooled[0], pooled[1]


def eva_cache_attention(q, ring_k, ring_v, sum_k, sum_v, n_ring, n_sum,
                        scale: float):
    """q [B, H, Dh] over ring rows [0, n_ring[b]) and summary rows [0,
    n_sum[b]) under one softmax: [B, H, Dh] in q's dtype (zeros where a
    lane reads nothing). Scores and sums float32; the caches are never
    cast or copied."""

    def scores(keys, n):
        s = jnp.einsum("bhd,bhtd->bht", q, keys,
                       preferred_element_type=jnp.float32) * scale
        col = jnp.arange(keys.shape[2], dtype=jnp.int32)
        return jnp.where(col[None, None, :] < n[:, None, None], s, NEG_INF)

    s = jnp.concatenate([scores(ring_k, n_ring), scores(sum_k, n_sum)], -1)
    m = s.max(axis=-1, keepdims=True)
    p = jnp.where(s > NEG_INF, jnp.exp(s - m), 0.0)
    l = p.sum(axis=-1, keepdims=True)
    p = (p / jnp.where(l == 0.0, 1.0, l)).astype(q.dtype)
    w = ring_k.shape[2]
    o = jnp.einsum("bht,bhtd->bhd", p[..., :w], ring_v,
                   preferred_element_type=jnp.float32)
    o = o + jnp.einsum("bht,bhtd->bhd", p[..., w:], sum_v,
                       preferred_element_type=jnp.float32)
    return o.astype(q.dtype)


def _eva_kernel(nr_ref, ns_ref, wpos_ref, q_ref, knew_ref, vnew_ref, _k_in,
                _v_in, sk_hbm, sv_hbm, o_ref, k_hbm, v_hbm, kstage, vstage,
                kbuf, vbuf, sem, wsem, rsem, *, block, scale):
    """The whole batch of one layer (``ops.decode_attention._ragged_kernel``
    with a second pair of arrays the walk goes on into).

    nr_ref, ns_ref, wpos_ref: SMEM [B]: ring rows and summary rows a lane
    reads, and the ring row its new K and V go to (outside [0, W): none);
    q_ref / o_ref: VMEM [B, H, 1, Dh]; knew_ref / vnew_ref: VMEM [B, H, 1,
    Dh]; k_hbm / v_hbm: the layer's ring [B, H, W, Dh], left where it is
    (the call's aliased outputs; ``_k_in`` / ``_v_in`` are the same
    buffers); sk_hbm / sv_hbm: its summaries [B, H, Ns, Dh], read only;
    kstage / vstage: VMEM [B, H, chunk, Dh], outputs: a writing lane's
    patched chunk, on its way to the ring and out of the call (a lane that
    writes nothing leaves its rows as they were allocated); kbuf / vbuf:
    VMEM [2, H, block, Dh]; sem [2 (k, v), 2] the reads', wsem [2] the
    writes', rsem [2] the fetch of a chunk that no block held.
    """
    n_lanes, n_heads, _one, dh = q_ref.shape
    w = k_hbm.shape[2]
    chunk = kstage.shape[2]     # the write's unit is the model's chunk

    def blocks(n):
        return (n + block - 1) // block

    def copies(lane, i, slot, remote):
        """The copies of the lane's ``i``-th block of one kind into
        ``slot``: both kinds' blocks have one shape, so either pair waits
        for the other's bytes."""
        start = pl.multiple_of(jnp.asarray(i, jnp.int32) * block, block)
        src_k, src_v = (sk_hbm, sv_hbm) if remote else (k_hbm, v_hbm)
        return (
            pltpu.make_async_copy(
                src_k.at[lane, :, pl.ds(start, block), :], kbuf.at[slot],
                sem.at[0, slot]),
            pltpu.make_async_copy(
                src_v.at[lane, :, pl.ds(start, block), :], vbuf.at[slot],
                sem.at[1, slot]),
        )

    def start(lane, i, slot):
        """Start the ``i``-th block of the lane's walk: a ring block, or
        past them a summary block."""
        nb_r = blocks(nr_ref[lane])

        @pl.when(i < nb_r)
        def _():
            for c in copies(lane, i, slot, False):
                c.start()

        @pl.when(i >= nb_r)
        def _():
            for c in copies(lane, i - nb_r, slot, True):
                c.start()

    def write_back(which, lane, wp):
        stage, hbm = ((kstage, k_hbm), (vstage, v_hbm))[which]
        group = pl.multiple_of(wp // chunk * chunk, chunk)
        return pltpu.make_async_copy(
            stage.at[lane], hbm.at[lane, :, pl.ds(group, chunk), :],
            wsem.at[which])

    def patched(which, lane, wp, group):
        """``group`` [H, chunk, Dh] with the lane's new row at ``wp``."""
        row = lax.broadcasted_iota(jnp.int32, group.shape, 1)
        return jnp.where(
            row == wp % chunk, (knew_ref, vnew_ref)[which][lane], group)

    def land(which, lane, wp, slot):
        """The ring block in ``slot`` holds row ``wp`` and its read is
        done: the new row replaces the stale one there, and its group
        starts back to the ring (nobody waits for it before the end)."""
        buf, stage = ((kbuf, kstage), (vbuf, vstage))[which]
        at = pl.ds(pl.multiple_of(wp % block // chunk * chunk, chunk), chunk)
        group = patched(which, lane, wp, buf[slot, :, at, :])
        buf[slot, :, at, :] = group
        stage[lane] = group
        write_back(which, lane, wp).start()

    def land_unread(which, lane, wp):
        """No block of the lane's read held ``wp``: its group comes from
        the ring into the staging buffer, takes the row and goes back."""
        stage, hbm = ((kstage, k_hbm), (vstage, v_hbm))[which]
        group = pl.multiple_of(wp // chunk * chunk, chunk)
        fetch = pltpu.make_async_copy(
            hbm.at[lane, :, pl.ds(group, chunk), :], stage.at[lane],
            rsem.at[which])
        fetch.start()
        fetch.wait()
        stage[lane] = patched(which, lane, wp, stage[lane])
        write_back(which, lane, wp).start()

    def next_live(lane):
        """The first lane after ``lane`` that reads anything, or B (a lane
        with no ring row reads nothing: its summaries are never alone)."""
        return lax.while_loop(
            lambda b: (b < n_lanes)
            & (nr_ref[jnp.minimum(b, n_lanes - 1)] <= 0),
            lambda b: b + 1, lane + 1)

    first = next_live(jnp.int32(-1))

    @pl.when(first < n_lanes)
    def _():
        start(first, 0, 0)

    def lane_body(lane, carry):
        done_blocks, written = carry
        nr = nr_ref[lane]
        ns = jnp.where(nr > 0, ns_ref[lane], 0)
        nb_r = blocks(nr)
        n_blocks = nb_r + blocks(ns)
        wp = wpos_ref[lane]
        writes = (nr > 0) & (wp >= 0) & (wp < w)
        w_block = jnp.where(writes & (wp // block < nb_r), wp // block, -1)
        q = q_ref[lane]  # [H, 1, Dh]

        def block_body(i, carry):
            o, m, l = carry
            slot = (done_blocks + i) % 2

            # the copy after this one: this lane's next block of either
            # kind, or the next live lane's first
            @pl.when(i + 1 < n_blocks)
            def _():
                start(lane, i + 1, 1 - slot)

            @pl.when(i + 1 == n_blocks)
            def _():
                nxt = next_live(lane)

                @pl.when(nxt < n_lanes)
                def _():
                    start(nxt, 0, 1 - slot)

            k_copy, v_copy = copies(lane, 0, slot, False)
            k_copy.wait()

            @pl.when(i == w_block)
            def _():
                land(0, lane, wp, slot)

            s = jnp.einsum(
                "grd,gkd->grk", q, kbuf[slot],
                preferred_element_type=jnp.float32,
            ) * scale  # [H, 1, block]
            remote = i >= nb_r
            col = (jnp.where(remote, i - nb_r, i) * block
                   + lax.broadcasted_iota(jnp.int32, s.shape, 2))
            # the lane's first ring row is live in its first block, so m is
            # finite from there on and a masked entry's exp underflows to 0
            s = jnp.where(col < jnp.where(remote, ns, nr), s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new)
            l = l * alpha + p.sum(axis=-1, keepdims=True)
            v_copy.wait()

            @pl.when(i == w_block)
            def _():
                land(1, lane, wp, slot)

            o = o * alpha + jnp.einsum(
                "grk,gkd->grd", p.astype(vbuf.dtype), vbuf[slot],
                preferred_element_type=jnp.float32,
            )
            return o, m_new, l

        o, _, l = lax.fori_loop(
            0, n_blocks, block_body,
            (jnp.zeros((n_heads, 1, dh), jnp.float32),
             jnp.full((n_heads, 1, 1), NEG_INF, jnp.float32),
             jnp.zeros((n_heads, 1, 1), jnp.float32)),
        )
        # a lane that read nothing ran no block: o = 0, l = 0, zeros out
        o_ref[lane] = (o / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)

        @pl.when(writes & (w_block < 0))
        def _():
            land_unread(0, lane, wp)
            land_unread(1, lane, wp)

        return done_blocks + n_blocks, written + writes.astype(jnp.int32)

    _, written = lax.fori_loop(
        0, n_lanes, lane_body, (jnp.int32(0), jnp.int32(0)))

    # the next layer-step of this cache is a later call: every write has
    # landed when this one returns (each wait takes one group's bytes)
    def drain(_, carry):
        write_back(0, 0, 0).wait()
        write_back(1, 0, 0).wait()
        return carry

    lax.fori_loop(0, written, drain, 0)


@functools.partial(jax.jit,
                   static_argnames=("scale", "chunk", "block", "interpret"))
def ragged_eva_attention(q, ring_k, ring_v, sum_k, sum_v, n_ring, n_sum,
                         k_new, v_new, write_pos, *, scale: float, chunk: int,
                         block: int = EVA_BLOCK, interpret: bool = False):
    """The Pallas kernel. q [B, H, Dh]; ring_k, ring_v [B, H, W, Dh] and
    sum_k, sum_v [B, H, Ns, Dh] one layer's cache, unsliced (``W`` and
    ``Ns`` multiples of ``block``); n_ring, n_sum [B] int32 the rows lane b
    reads of each kind (clamped to the arrays); k_new, v_new [B, H, Dh]
    this step's rows and write_pos [B] the ring row they go to. Returns
    ``(o [B, H, Dh], ring_k, ring_v, chunk_k, chunk_v)``: the ring is
    aliased in and out, so under a caller that donates it nothing but the
    rows' chunks moves; ``chunk_k``, ``chunk_v`` [B, H, chunk, Dh] are the
    ring's rows of the chunk that holds ``write_pos``, the new row among
    them, for a lane that writes (anything at all for one that does not).

    Lane b first takes its new row at ``write_pos[b]``, then attends to
    ring rows [0, n_ring[b]) and summary rows [0, n_sum[b]) under one
    softmax: the read of the ring ``cache_write()`` would have made, bit
    for bit. A ``write_pos`` outside [0, W) is dropped, and a lane with
    ``n_ring[b] == 0`` reads nothing of either kind, gives zeros and WRITES
    NOTHING (``ops.decode_attention.ragged_decode_attention``'s contract)."""
    b, h, dh = q.shape
    w, ns = ring_k.shape[2], sum_k.shape[2]
    if (ring_k.shape[:2] != (b, h) or sum_k.shape[:2] != (b, h)
            or w % block or ns % block or chunk % GROUP or block % chunk
            or dh % 128):
        raise ValueError(
            f"q {q.shape} / ring {ring_k.shape} / summaries {sum_k.shape} / "
            f"chunk {chunk} do not fit the kernel (one head count, a head of "
            f"whole registers, both lengths multiples of {block}, a chunk of "
            f"whole groups of {GROUP})")
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    out, ring_k, ring_v, chunk_k, chunk_v = pl.pallas_call(
        functools.partial(_eva_kernel, block=block, scale=float(scale)),
        out_shape=(
            jax.ShapeDtypeStruct((b, h, 1, dh), q.dtype),
            jax.ShapeDtypeStruct(ring_k.shape, ring_k.dtype),
            jax.ShapeDtypeStruct(ring_v.shape, ring_v.dtype),
            jax.ShapeDtypeStruct((b, h, chunk, dh), ring_k.dtype),
            jax.ShapeDtypeStruct((b, h, chunk, dh), ring_v.dtype),
        ),
        in_specs=[smem, smem, smem, vmem, vmem, vmem, hbm, hbm, hbm, hbm],
        out_specs=(vmem, hbm, hbm, vmem, vmem),
        input_output_aliases={6: 1, 7: 2},
        scratch_shapes=[
            pltpu.VMEM((2, h, block, dh), ring_k.dtype),
            pltpu.VMEM((2, h, block, dh), ring_v.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        interpret=interpret,
        name="eva_decode_attention",
    )(jnp.clip(n_ring.astype(jnp.int32), 0, w),
      jnp.clip(n_sum.astype(jnp.int32), 0, ns),
      write_pos.astype(jnp.int32), q[:, :, None, :],
      k_new.astype(ring_k.dtype)[:, :, None, :],
      v_new.astype(ring_v.dtype)[:, :, None, :],
      ring_k, ring_v, sum_k, sum_v)
    return out[:, :, 0], ring_k, ring_v, chunk_k, chunk_v


@functools.partial(jax.jit, static_argnames=("scale", "chunk", "mesh"))
def eva_decode_attention(q, ring_k, ring_v, sum_k, sum_v, k_new, v_new,
                         write_pos, n_ring, n_sum, *, scale: float,
                         chunk: int, mesh=None):
    """The decode step's write and read of one EVA layer's cache: this
    step's rows k_new, v_new [B, H, Dh] go into the UNSLICED ring [B, H, W,
    Dh] at ``write_pos`` [B] (outside [0, W): dropped), then q [B, H, Dh]
    attends to ring rows [0, n_ring[b]) and summary rows [0, n_sum[b])
    under one softmax. Returns ``(o [B, H, Dh], ring_k, ring_v, chunk_k,
    chunk_v)``, the last two [B, H, chunk, Dh]: the ring's rows of the
    aligned chunk that holds ``write_pos``, after the write (of a lane
    that writes; nobody reads another lane's).

    The kernel skips a lane of ``n_ring == 0`` (zeros out, no write); the
    scatter and the dots write every lane's row that ``write_pos`` admits
    and read both arrays whole under the masks, with zeros out for such a
    lane too. Where ``n_ring > 0`` the two agree to rounding, the ring bit
    for bit (tests/test_eva_attention.py).

    Jitted, so the burst's unrolled layers lower it once and call it."""

    def dots(q, ring_k, ring_v, sum_k, sum_v, k_new, v_new, write_pos,
             n_ring, n_sum):
        ring_k = cache_write(ring_k, k_new[:, :, None], write_pos[:, None])
        ring_v = cache_write(ring_v, v_new[:, :, None], write_pos[:, None])
        o = eva_cache_attention(q, ring_k, ring_v, sum_k, sum_v, n_ring,
                                jnp.where(n_ring > 0, n_sum, 0), scale)
        at = jnp.clip(write_pos, 0, ring_k.shape[2] - 1) // chunk * chunk

        def rows(ring):
            return jax.vmap(lambda a, s: lax.dynamic_slice_in_dim(
                a, s, chunk, axis=1))(ring, at)

        return o, ring_k, ring_v, rows(ring_k), rows(ring_v)

    def kernel(q, ring_k, ring_v, sum_k, sum_v, k_new, v_new, write_pos,
               n_ring, n_sum):
        return ragged_eva_attention(
            q, ring_k, ring_v, sum_k, sum_v, n_ring, n_sum, k_new, v_new,
            write_pos, scale=scale, chunk=chunk, block=EVA_BLOCK)

    args = (q, ring_k, ring_v, sum_k, sum_v, k_new, v_new, write_pos,
            n_ring, n_sum)
    with jax.named_scope("eva_decode_attention"):
        # the platform is known only when this is lowered: ask whether a
        # lowering for a TPU takes the kernel, and let that lowering choose
        if not eva_reads_ragged(
                "tpu", q.shape, ring_k.shape, sum_k.shape,
                (q.dtype, ring_k.dtype, ring_v.dtype, sum_k.dtype,
                 sum_v.dtype), chunk, mesh):
            return dots(*args)
        return lax.platform_dependent(*args, tpu=kernel, default=dots)
