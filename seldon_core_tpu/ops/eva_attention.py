"""Single-position decode attention over an EVA cache, the step's writes
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
CHUNK (its aligned group of ``chunk`` positions) goes back to the ring.

**The step that completes a chunk pools it here too.** The kernel has the
patched chunk in VMEM when it lands the row, so for a lane whose
``sum_at`` is a row of the summaries it computes ``chunk_summary`` of that
chunk there (the layer's ``mu_k`` and ``phi`` are two more VMEM operands;
logits, softmax and sums float32, the rows cast to the cache's dtype) and
lands the two pooled rows itself. One row of a bfloat16 array is half of
its packed words, which no copy moves alone: the row's aligned group of
``GROUP`` summary rows is fetched beside the lane's walk, takes the row and
goes back (``[H, GROUP, Dh]`` each way a kind: the ring's way for a row no
block held). No hazard with the walk: a lane reads summary rows ``[0,
(W / C) floor(t / W))``, whole blocks of earlier windows, and writes row
``t // C`` of the current window's block. All four arrays are aliased in
and out of the call, so under the burst's donation nothing of them moves
but the groups written. (Outside the kernel the write is a scatter of every
lane's row, fifteen in sixteen of them dropped, a tenth of a step on a v5e:
PERF.md section 6, PR 45; and a gather of the chunk from the ring would
have the compiler carry the ring through the burst in another layout, a
ring-sized copy a layer and step.) A lane that reads nothing copies
nothing, gives zeros and writes nothing.

``eva_decode_attention()`` is the public entry (the kernel where
``eva_reads_ragged`` holds for the platform the executable is lowered for,
the scatters, four dots and ``chunk_summary`` elsewhere: the same
arithmetic in ``jax.numpy``), under ``jax.named_scope("eva_decode_attention")``.
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


def _eva_kernel(nr_ref, ns_ref, wpos_ref, sat_ref, q_ref, knew_ref, vnew_ref,
                mu_ref, phi_ref, _k_in, _v_in, _sk_in, _sv_in, o_ref, k_hbm,
                v_hbm, sk_hbm, sv_hbm, kstage, vstage, sstage, kbuf, vbuf, sem,
                wsem, rsem, ssem, swsem, *, block, scale):
    """The whole batch of one layer (``ops.decode_attention._ragged_kernel``
    with a second pair of arrays the walk goes on into, and a second write).

    nr_ref, ns_ref, wpos_ref, sat_ref: SMEM [B]: ring rows and summary rows
    a lane reads, the ring row its new K and V go to (outside [0, W): none)
    and the summary row its completed chunk's pooled rows go to (outside
    [0, Ns): none); q_ref / o_ref: VMEM [B, H, 1, Dh]; knew_ref / vnew_ref:
    VMEM [B, H, 1, Dh]; mu_ref / phi_ref: VMEM [H, Dh], the layer's pooling
    vectors; k_hbm / v_hbm: the layer's ring [B, H, W, Dh] and sk_hbm /
    sv_hbm: its summaries [B, H, Ns, Dh], all four left where they are (the
    call's aliased outputs; ``_k_in`` ... ``_sv_in`` are the same buffers); kstage / vstage: VMEM [B, H, chunk, Dh]: a writing
    lane's patched chunk, on its way to the ring and, where it completes,
    to the pooling; sstage: VMEM [2 (k, v), B, H, GROUP, Dh]: the aligned
    group of summary rows the pooled row joins; kbuf / vbuf: VMEM [2, H,
    block, Dh]; sem [2 (k, v), 2] the reads', wsem [2] the ring writes',
    rsem [2] the fetch of a chunk that no block held, ssem [2] the fetch of
    a summary group, swsem [2] the summary writes'.
    """
    n_lanes, n_heads, _one, dh = q_ref.shape
    w = k_hbm.shape[2]
    n_sum_rows = sk_hbm.shape[2]
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

    def summary_copy(which, lane, at, back):
        """The fetch (``back``: the write-back) of the group of ``GROUP``
        summary rows that holds row ``at``: a row alone is half of its
        packed words (the ring's way, ``land_unread``)."""
        group = pl.multiple_of(at // GROUP * GROUP, GROUP)
        rows = (sk_hbm, sv_hbm)[which].at[lane, :, pl.ds(group, GROUP), :]
        stage = sstage.at[which, lane]
        if back:
            return pltpu.make_async_copy(stage, rows, swsem.at[which])
        return pltpu.make_async_copy(rows, stage, ssem.at[which])

    def pool(lane, at):
        """The lane's step completed its chunk: ``chunk_summary`` of the
        patched chunk in the stages (float32 logits, softmax and sums, the
        rows cast to the cache's dtype) into row ``at`` of both summary
        groups, fetched since the lane's walk began, and back they go."""
        k32 = kstage[lane].astype(jnp.float32)   # [H, chunk, Dh]
        for which, (by_ref, rows) in enumerate((
                (mu_ref, k32), (phi_ref, vstage[lane].astype(jnp.float32)))):
            by = by_ref[...].astype(jnp.float32)[:, None, :]
            logits = (k32 * by).sum(axis=-1, keepdims=True) * scale
            p = jnp.exp(logits - logits.max(axis=1, keepdims=True))
            p = p / p.sum(axis=1, keepdims=True)
            pooled = (p * rows).sum(axis=1, keepdims=True)   # [H, 1, Dh]
            summary_copy(which, lane, at, False).wait()
            group = sstage[which, lane]
            row = lax.broadcasted_iota(jnp.int32, group.shape, 1)
            sstage[which, lane] = jnp.where(
                row == at % GROUP, pooled.astype(group.dtype), group)
            summary_copy(which, lane, at, True).start()

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
        done_blocks, written, n_pooled = carry
        nr = nr_ref[lane]
        ns = jnp.where(nr > 0, ns_ref[lane], 0)
        nb_r = blocks(nr)
        n_blocks = nb_r + blocks(ns)
        wp = wpos_ref[lane]
        writes = (nr > 0) & (wp >= 0) & (wp < w)
        w_block = jnp.where(writes & (wp // block < nb_r), wp // block, -1)
        at = sat_ref[lane]
        pools = writes & (at >= 0) & (at < n_sum_rows)
        q = q_ref[lane]  # [H, 1, Dh]

        # the summary groups come beside the walk (the row written lies in
        # the current window's block, which no lane's walk streams)
        @pl.when(pools)
        def _():
            for which in range(2):
                summary_copy(which, lane, at, False).start()

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

        @pl.when(pools)
        def _():
            pool(lane, at)

        return (done_blocks + n_blocks, written + writes.astype(jnp.int32),
                n_pooled + pools.astype(jnp.int32))

    _, written, n_pooled = lax.fori_loop(
        0, n_lanes, lane_body, (jnp.int32(0), jnp.int32(0), jnp.int32(0)))

    # the next layer-step of this cache is a later call: every write has
    # landed when this one returns (each wait takes one group's bytes)
    def drain(n, copies):
        def body(_, carry):
            for copy in copies:
                copy.wait()
            return carry

        lax.fori_loop(0, n, body, 0)

    drain(written, [write_back(which, 0, 0) for which in range(2)])
    drain(n_pooled, [summary_copy(which, 0, 0, True) for which in range(2)])


@functools.partial(jax.jit,
                   static_argnames=("scale", "chunk", "block", "interpret"))
def ragged_eva_attention(q, ring_k, ring_v, sum_k, sum_v, n_ring, n_sum,
                         k_new, v_new, write_pos, mu, phi, sum_at, *,
                         scale: float, chunk: int, block: int = EVA_BLOCK,
                         interpret: bool = False):
    """The Pallas kernel. q [B, H, Dh]; ring_k, ring_v [B, H, W, Dh] and
    sum_k, sum_v [B, H, Ns, Dh] one layer's cache, unsliced (``W`` and
    ``Ns`` multiples of ``block``); n_ring, n_sum [B] int32 the rows lane b
    reads of each kind (clamped to the arrays); k_new, v_new [B, H, Dh]
    this step's rows and write_pos [B] the ring row they go to; mu, phi [H,
    Dh] the layer's pooling vectors and sum_at [B] the summary row a lane
    whose step completes its chunk writes (outside [0, Ns): none). Returns
    ``(o [B, H, Dh], ring_k, ring_v, sum_k, sum_v)``: all four arrays are
    aliased in and out, so under a caller that donates them nothing moves
    but the ring rows' chunks and the summary rows' groups of ``GROUP``.

    Lane b first takes its new row at ``write_pos[b]``, then attends to
    ring rows [0, n_ring[b]) and summary rows [0, n_sum[b]) under one
    softmax: the read of the ring ``cache_write()`` would have made, bit
    for bit; then, with ``sum_at[b]`` in range, ``chunk_summary`` of the
    aligned chunk of the ring that holds ``write_pos[b]``, the new row in
    it, goes to row ``sum_at[b]`` of both summary arrays. A ``write_pos``
    outside [0, W) is dropped and its lane pools nothing, and a lane with
    ``n_ring[b] == 0`` reads nothing of either kind, gives zeros and WRITES
    NOTHING in either (``ops.decode_attention.ragged_decode_attention``'s
    contract)."""
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

    def int32(a):
        return lax.convert_element_type(a, jnp.int32)

    def rows(a, dtype):
        """[..., H, Dh] -> [..., H, 1, Dh]: a row a head, as the kernel
        broadcasts it over a group's rows."""
        return lax.expand_dims(lax.convert_element_type(a, dtype), (a.ndim - 1,))

    out, ring_k, ring_v, sum_k, sum_v = pl.pallas_call(
        functools.partial(_eva_kernel, block=block, scale=float(scale)),
        out_shape=(
            jax.ShapeDtypeStruct((b, h, 1, dh), q.dtype),
            jax.ShapeDtypeStruct(ring_k.shape, ring_k.dtype),
            jax.ShapeDtypeStruct(ring_v.shape, ring_v.dtype),
            jax.ShapeDtypeStruct(sum_k.shape, sum_k.dtype),
            jax.ShapeDtypeStruct(sum_v.shape, sum_v.dtype),
        ),
        in_specs=[smem] * 4 + [vmem] * 5 + [hbm] * 4,
        out_specs=(vmem, hbm, hbm, hbm, hbm),
        input_output_aliases={9: 1, 10: 2, 11: 3, 12: 4},
        scratch_shapes=[
            pltpu.VMEM((b, h, chunk, dh), ring_k.dtype),
            pltpu.VMEM((b, h, chunk, dh), ring_v.dtype),
            pltpu.VMEM((2, b, h, GROUP, dh), sum_k.dtype),
            pltpu.VMEM((2, h, block, dh), ring_k.dtype),
            pltpu.VMEM((2, h, block, dh), ring_v.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        interpret=interpret,
        name="eva_decode_attention",
    )(lax.clamp(jnp.int32(0), int32(n_ring), jnp.int32(w)),
      lax.clamp(jnp.int32(0), int32(n_sum), jnp.int32(ns)),
      int32(write_pos), int32(sum_at), rows(q, q.dtype),
      rows(k_new, ring_k.dtype), rows(v_new, ring_v.dtype),
      mu, phi, ring_k, ring_v, sum_k, sum_v)
    return lax.squeeze(out, (2,)), ring_k, ring_v, sum_k, sum_v


@functools.partial(jax.jit, static_argnames=("scale", "chunk", "mesh"))
def eva_decode_attention(q, ring_k, ring_v, sum_k, sum_v, k_new, v_new,
                         write_pos, n_ring, n_sum, mu, phi, sum_at, *,
                         scale: float, chunk: int, mesh=None):
    """The decode step's writes and read of one EVA layer's cache: this
    step's rows k_new, v_new [B, H, Dh] go into the UNSLICED ring [B, H, W,
    Dh] at ``write_pos`` [B] (outside [0, W): dropped), then q [B, H, Dh]
    attends to ring rows [0, n_ring[b]) and summary rows [0, n_sum[b])
    under one softmax, and a lane whose step completes a chunk pools it
    (``chunk_summary`` of the ring's aligned ``chunk`` rows that hold
    ``write_pos``, after the write, under the layer's ``mu`` and ``phi``
    [H, Dh]) into row ``sum_at`` [B] of the UNSLICED summaries [B, H, Ns,
    Dh] (outside [0, Ns): none). Returns ``(o [B, H, Dh], ring_k, ring_v,
    sum_k, sum_v)``.

    The kernel skips a lane of ``n_ring == 0`` (zeros out, no write of
    either kind) and pools nothing for a lane whose ``write_pos`` is
    dropped; the scatters and the dots write every lane's rows that
    ``write_pos`` and ``sum_at`` admit and read both arrays whole under the
    masks, with zeros out for such a lane too. Where ``n_ring > 0`` the two
    agree to rounding, the ring bit for bit (tests/test_eva_attention.py).

    Jitted, so the burst's unrolled layers lower it once and call it."""

    def dots(q, ring_k, ring_v, sum_k, sum_v, k_new, v_new, write_pos,
             n_ring, n_sum, mu, phi, sum_at):
        ring_k = cache_write(ring_k, k_new[:, :, None], write_pos[:, None])
        ring_v = cache_write(ring_v, v_new[:, :, None], write_pos[:, None])
        o = eva_cache_attention(q, ring_k, ring_v, sum_k, sum_v, n_ring,
                                jnp.where(n_ring > 0, n_sum, 0), scale)
        at = jnp.clip(write_pos, 0, ring_k.shape[2] - 1) // chunk * chunk

        def rows(ring):
            return jax.vmap(lambda a, s: lax.dynamic_slice_in_dim(
                a, s, chunk, axis=1))(ring, at)[:, :, None]

        # pooled for every lane; ``sum_at`` drops all but the completed
        pooled_k, pooled_v = chunk_summary(
            rows(ring_k), rows(ring_v), mu, phi, scale)
        return (o, ring_k, ring_v,
                cache_write(sum_k, pooled_k, sum_at[:, None]),
                cache_write(sum_v, pooled_v, sum_at[:, None]))

    def kernel(q, ring_k, ring_v, sum_k, sum_v, k_new, v_new, write_pos,
               n_ring, n_sum, mu, phi, sum_at):
        return ragged_eva_attention(
            q, ring_k, ring_v, sum_k, sum_v, n_ring, n_sum, k_new, v_new,
            write_pos, mu, phi, sum_at, scale=scale, chunk=chunk,
            block=EVA_BLOCK)

    args = (q, ring_k, ring_v, sum_k, sum_v, k_new, v_new, write_pos,
            n_ring, n_sum, mu, phi, sum_at)
    with jax.named_scope("eva_decode_attention"):
        # the platform is known only when this is lowered: ask whether a
        # lowering for a TPU takes the kernel, and let that lowering choose
        if not eva_reads_ragged(
                "tpu", q.shape, ring_k.shape, sum_k.shape,
                (q.dtype, ring_k.dtype, ring_v.dtype, sum_k.dtype,
                 sum_v.dtype), chunk, mesh):
            return dots(*args)
        return lax.platform_dependent(*args, tpu=kernel, default=dots)
