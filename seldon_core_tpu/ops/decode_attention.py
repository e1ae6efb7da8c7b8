"""Single-position decode attention over the KV cache, the step's write
included: a ragged Pallas TPU kernel that lands each live lane's new row
and streams the lane's own length, and the scatter with the two XLA dots.

Motivation: a decode burst reads the cache through one static bucket
(``attn_len`` >= the deepest lane's end-of-burst position), so the dots
read every lane's whole bucket: idle lanes and the tail past a lane's
position included. On a part-loaded replica that is 90-97% of the KV
bytes (ISSUE 30). The kernel takes ``lens [B]`` and copies
``ceil(len / block)`` blocks of K and of V per lane from HBM; a lane of
length 0 issues no copy.

The write (ISSUE 32): before the kernel took it, this step's K and V row
of every lane went into the cache by one ``lax.scatter`` each, 19-20 us
apiece for 224-256 rows of 256 B whatever the number of live lanes:
twice the time of the read they fed at a part-loaded 28 lanes. The
kernel has the block that holds the row in VMEM anyway (a live lane
writes where its read ends), so it puts the row there before the scores
and copies the row's aligned group of ``GROUP`` positions back to the
cache, which is aliased in and out of the call. ``cache_write()`` is the
scatter, for everything that is not this step.

The earlier kernel that lost here (23.7 ms a step against the dots' 6.0,
16 lanes x 1920 keys) had a grid of (lanes, chunks) programs with
``[block_k, Dh]`` chunks per head: program overhead x (layers x lanes x
chunks). This one is ONE program per layer: the walk over lanes and
over a lane's blocks are loops inside it, a block is ``block`` positions
x all KV heads (``walk_block``: 128 keys doubled until a K and V pair
copies 512 KiB, 1,024 keys at most),
double-buffered across lane boundaries, so the next lane's first block
is in flight while this lane's last one is computed.

``decode_attention()`` is the public entry for "write this step's row,
then read": it picks the kernel by what it can see (``T == 1``,
``head_dim`` a multiple of 128 or cut at 128 with its rest packed
(``packed_key_rows``: ISSUE 59), the cache length a multiple of the
block, no serving mesh) and by the platform the executable is LOWERED
for (``lax.platform_dependent``; a process whose backend is the CPU can
compile for a described TPU and gets the kernel), and takes
``cache_write()`` twice and ``cache_attention()``'s dots everywhere
else. That rule is ``reads_ragged()``; the scheduler asks it too,
because a read that bounds itself per lane needs no static bucket and so
no executable per bucket. ``ragged_decode_attention()`` is the kernel
itself (``interpret`` runs it on the CPU for the equivalence tests).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# A row of the K and V arrays as the kernel slices it: a register's lanes.
LANES = 128
# Positions a block of the walk holds where a block of them covers the
# chain (``walk_block``); also the scheduler's bucket step (``attn_bucket``),
# so every bucket tiles.
BLOCK = 128
# Positions the in-kernel write copies back to the cache around the new
# row. One bfloat16 row cannot be addressed: two rows share a 32-bit
# sublane, and Mosaic (jax 0.9.0, for a v5e) refuses a 1-row slice of a
# ref ("slice shape along dimension 2 must be aligned to tiling") and a
# dynamic vector load of 2 rows ("cannot statically prove that index in
# dimension 2 is a multiple of 8"). So the kernel writes the row's
# aligned group of 8: one T(8,128)(2,1) tile of the cache as XLA lays it
# out in HBM, 16 KB a lane for K over 8 KV heads and as much for V.
GROUP = 8
# The walk's block, one curve on a v5e: COPY AGAINST CHAIN. An iteration of
# the walk is a copy of the block's K and V (``2 x KV x Dh x itemsize x
# keys`` bytes at 819 GB/s) and then a chain (wait, scores, max, exp, sum,
# product) of ~0.6 us whatever the block holds; a lane's length rounds up to
# the block, so the smallest block whose copy covers the chain is the one to
# walk. The readings (my chip runs), at 128 / 256 / 512 keys a block:
# - PR 30, 8 KV heads of 128 in bfloat16 (128 keys: 512 KiB, 0.64 us), ms a
#   step of the burst: 8 of 28 lanes live under a 1280 bucket 7.52 / 7.47 /
#   7.60, 28 of 28 at 64-560 under 640 8.43 / 8.66 / 8.81, Mistral 32 of 32
#   12.24 / 12.37 / 12.63: the copy covers the chain at 128, and the smaller
#   block reads less.
# - PR 49, 4 KV heads of 128 (128 keys: 256 KiB, 0.32 us), the block pass of
#   4 positions a lane (32 lanes at 0.3k-3.3k of a cache of 4096): 1.48 /
#   1.00 / 1.00 ms a pass of 6 calls in the cell's capture; alone 0.68 / 0.49
#   / 0.46 us a 128 keys; 512 streams 114% of the live rows for 256's 108%
#   and is no faster; walking the KV heads one or two at a time inside a
#   block is slower at every size. The query rows hardly matter (the same
#   cache at 8 / 16 / 32 rows a KV head: 0.63 / 0.65 / 0.69 us a 128 keys at
#   128, 0.47 / 0.48 / 0.50 at 256).
# - PR 50, ONE position a lane (8 query rows a KV head) at the longbatch
#   cells' shapes and lengths, alone and host-timed, ms a call: 4 KV heads of
#   128 under a window of 2048 (the walk starts at the block that holds ``len
#   - window``, so both of a window's edges round) 0.226-0.229 / 0.177-0.181
#   / 0.189-0.191, without one 0.274-0.280 / 0.210-0.214 / 0.215-0.220; 2 KV
#   heads of 256 0.396-0.402 / 0.301-0.311 / 0.310-0.313; 8 KV heads of 128
#   at Mistral's lengths 0.245-0.251 / 0.246-0.256 / 0.269-0.274 (at
#   InternLM's 28 lanes of 65-1024 alone 0.116-0.119 / 0.101-0.103 /
#   0.098-0.100, which PR 30's burst above did not see: PERF.md section 7).
# - PR 55 (call 4), ONE KV head of 128 (128 keys: 64 KiB, 0.08 us), one
#   position a lane, alone at jamba's 192 lanes of 90-2,281 of a cache of 8192,
#   ms a call at 128 / 256 / 512 / 1,024: 0.862 / 0.585 / 0.461 / 0.406: the
#   chain, not the copy, sets the pace until the block copies ``COVERS``.
# - PR 61 (call 1), the same kernel IN the cell (jamba2-3b.thinking: two calls
#   a step, ~189 live lanes at a mean of ~1,050 keys; two traced runs each on
#   one machine), us a call at 256 / 512 / 1,024: 443.0, 439.1 / 284.3, 277.4 /
#   241.9, 235.1, streaming 1.12 / 1.24 / 1.49 of the lanes' live rows: 512 is
#   18% slower than 1,024 (PR 50's bar was 3%), so the ceiling is 1,024, where
#   one KV head's copy is ``COVERS`` and the kernel reads 75-81% of the rate.
# So the rule is on the BYTES a block copies: the block doubles from ``BLOCK``
# while its own copy is under ``COVERS``, to ``CEILING`` keys at most (no cell
# holds rows smaller than one KV head's: past 1,024 keys nothing was measured).
COVERS = 512 * 1024
CEILING = 1024


def walk_block(kv_heads: int, head_dim: int, dtype, t: int,
               v_dim: int = None) -> int:
    """Positions a block of the kernel's walk holds, for a cache [B,
    ``kv_heads``, ``t``, ``head_dim``] of ``dtype``: from ``BLOCK`` the block
    doubles while its own K and V are under ``COVERS`` bytes (their copy
    would not cover the iteration's chain) and the doubled block divides
    the cache's length, up to ``CEILING``. A rule on the call's shapes alone.
    Whoever states what the kernel streams (a counter, a roofline's
    bytes, the scheduler's ``kv_positions_read``) asks here. ``v_dim``: a
    value row's width where it is not the key row's. ``copied`` counts what
    a block's two copies MOVE a position: ``head_dim`` is what a KV head's key
    occupies of the K array, the row's width where a key is padded to one
    and the key's own where its rest is packed (``packed_key_rows``: a
    head of 192 in a row of 128 and half a row is 192 wide here)."""
    copied = (kv_heads * (head_dim + (v_dim or head_dim))
              * jnp.dtype(dtype).itemsize)
    block = BLOCK
    while (block < CEILING and copied * block < COVERS
           and t % (2 * block) == 0):
        block *= 2
    return block


def packed_key_rows(head_dim: int, kv_heads: int) -> int:
    """Rows of a K array's KV axis that hold the RESTS of keys wider than a
    row of ``LANES``: a key of ``LANES < head_dim < 2 x LANES`` is kept as a
    ``LANES``-wide part in its head's own row and a rest of ``r = head_dim
    - LANES``, and ``LANES / r`` heads' rests share a row after the
    ``kv_heads`` parts (``pack_keys``), so the array is ``[B, kv_heads +
    packed, T, LANES]`` and nothing of it is padding. 0 where the shapes do
    not divide so (``r`` divides ``LANES`` and ``kv_heads`` divides by
    ``LANES / r``): such keys are held in rows padded to a multiple of
    ``LANES``, which is the same kernel with no packed row. Mosaic slices
    no row of 192 (``block_reads_ragged``), which is why a key is cut at
    ``LANES``; a dot product is a sum over dims, so where the cut falls is
    free."""
    rest = head_dim - LANES
    if not 0 < rest < LANES or LANES % rest or kv_heads % (LANES // rest):
        return 0
    return kv_heads // (LANES // rest)


def pack_keys(k, packed: int):
    """Keys [..., KV, T, Dk] as a K array with ``packed`` rest rows holds
    them ([..., KV + packed, T, LANES]): each head's first ``LANES`` dims in
    its own row, then row ``j`` of the rests the dims past them of heads
    ``j x pack .. (j + 1) x pack - 1``, side by side. The stored bits."""
    *lead, kv, t, dk = k.shape
    pack, r = kv // packed, dk - LANES
    rests = k[..., LANES:].reshape(*lead, packed, pack, t, r)
    rests = jnp.moveaxis(rests, -3, -2).reshape(*lead, packed, t, pack * r)
    return jnp.concatenate([k[..., :LANES], rests], axis=-3)


def unpack_keys(rows, packed: int):
    """``pack_keys``'s inverse over the last three axes: [..., KV + packed,
    T, LANES] -> the keys whole, [..., KV, T, Dk]."""
    *lead, n, t, width = rows.shape
    kv = n - packed
    pack = kv // packed
    rests = rows[..., kv:, :, :].reshape(*lead, packed, t, pack, width // pack)
    rests = jnp.moveaxis(rests, -2, -3).reshape(*lead, kv, t, width // pack)
    return jnp.concatenate([rows[..., :kv, :, :], rests], axis=-1)


def _key_width(dh: int, n_kv: int, packed: int) -> int:
    """What a KV head's key occupies of a K array [B, n_kv + packed, T,
    dh]: its row and its share of a packed one."""
    return dh + dh * packed // n_kv


def reads_ragged(platform, q_shape, cache_shape, dtypes, mesh=None,
                 v_dim=None, packed=0) -> bool:
    """Whether ``decode_attention()``, lowered for ``platform``, reads
    each lane's own length (the kernel) and not a static bound of every
    lane (the dots). ``q_shape`` [B, H, T, Dh]; ``cache_shape`` [B, KV,
    Tc, Dh] of one layer's K (V's is the same, but for its rows' width
    ``v_dim`` where a caller's keys are wider than its values, and for the
    ``packed`` rows of K's KV axis that hold the keys' rests:
    ``packed_key_rows``; ``q_shape`` is then the queries' ``Dh``-wide
    part's); ``dtypes`` of q, K and V.

    The kernel wants one query position, a head size that fills lanes,
    whole GQA groups, whole blocks and one dtype; Mosaic kernels cannot
    be partitioned by GSPMD, so a serving mesh takes the dots."""
    return q_shape[2] == 1 and block_reads_ragged(
        platform, q_shape, cache_shape, dtypes, mesh, v_dim, packed)


def cache_attention(q, kc, vc, bound, dt, lo=None, sink=None):
    """Attention over the (sliced) KV cache with a key_pos <= bound
    mask, WITHOUT materialising a head-repeated cache copy. ``lo``
    (optional, ``bound``'s shape): the first key position a row sees, for
    a layer with a window; a row's band is then lo <= key_pos <= bound.

    ``jnp.repeat`` on the cache (the textbook GQA read) writes a
    rep-times-larger copy to HBM and reads it back — at 16 lanes /
    256-key windows that tripled the decode step's cache traffic and
    ran the read path ~7x below the HBM roof (measured on v5e:
    7.9 -> 5.7 ms/step at 256-key windows, 18.7 -> 9.2 at 1024, for a 1.26B model).
    Instead q is viewed as [B, KV, rep, T, Dh] and both dots batch
    over (B, KV), so the MXU consumes the grouped cache directly.

    ``bound``: [B] (single-position decode — every query row masks to
    its own prefix) or [B, T] (chunked decode — prefix + in-window
    causality). Scores accumulate in f32 (preferred_element_type);
    the bf16 cache is never cast or copied.

    ``vc`` may be narrower than ``kc`` ([B, KV, Ta, Dv]): the output is
    the values' width. ``sink`` ([H] float32, optional): a learned logit a
    query head that joins each softmax and has no value row, so that a
    row's weights sum to ``1 - p_sink``.
    """
    B, Hl, T, Dh = q.shape
    KVl, Ta = kc.shape[1], kc.shape[2]
    rep = Hl // KVl
    key_pos = jnp.arange(Ta, dtype=jnp.int32)
    if getattr(bound, "ndim", 0) == 2:  # [B, T]
        mask = key_pos[None, None, None, None, :] <= bound[:, None, None, :, None]
    else:  # [B]
        mask = key_pos[None, None, None, None, :] <= bound[:, None, None, None, None]
    if lo is not None:
        lo = lo[:, None, None, :, None] if lo.ndim == 2 else lo[:, None, None, None, None]
        mask = mask & (key_pos[None, None, None, None, :] >= lo)
    qg = q.reshape(B, KVl, rep, T, Dh)
    s = lax.dot_general(
        qg, kc, (((4,), (3,)), ((0, 1), (0, 1))),
        preferred_element_type=jnp.float32,
    ) / np.sqrt(Dh)  # [B, KV, rep, T, Ta]
    s = jnp.where(mask, s, NEG_INF)
    if sink is None:
        w = jax.nn.softmax(s, -1).astype(dt)
    else:
        col = jnp.broadcast_to(
            sink.astype(jnp.float32).reshape(1, KVl, rep, 1, 1),
            (B, KVl, rep, T, 1))
        w = jax.nn.softmax(jnp.concatenate([s, col], -1), -1)[..., :Ta].astype(dt)
    o = lax.dot_general(
        w, vc, (((4,), (2,)), ((0, 1), (0, 1))),
        preferred_element_type=jnp.float32,
    ).astype(dt)  # [B, KV, rep, T, Dv]
    return o.reshape(B, Hl, T, vc.shape[3])


def cache_write(cache, new, positions):
    """The ONE ragged cache write by scatter: ``new`` [B, KV, W, Dh] lands
    in ``cache`` [B, KV, T, Dh] at ``positions`` [B, W] (row b's column j
    at position positions[b, j]). A position >= T (or < 0) is DROPPED (JAX
    scatter semantics): the stop-aware bursts park finished lanes'
    writes there. The windows (speculation, chunked and prefix prefill),
    the stacked scan and every platform without the kernel write through
    this; the burst's single-position step on a TPU writes from inside
    ``ragged_decode_attention()``.

    Every (lane, KV head, position) is a scatter row of its own, so
    the window is the ``Dh`` vector alone: already the minor-most
    dimension of the cache as every other executable holds it. The
    textbook ``cache.at[rows, :, pos, :].set(...)`` has a [KV, Dh]
    window instead, and the TPU compiler then carries the cache
    through the burst's loop with KV minor to T: a cache-sized
    relayout copy of every layer's K and V on entry to the burst and
    another on exit, into scratch as large as the cache, whatever
    ``donate_argnums`` says (ISSUE 26: 27% of device time at 28 lanes
    x 24 layers). ``tools/burst_hlo_check.py`` compiles the burst and
    fails on such a copy; PERF.md section 6 has both outputs."""
    B, KV, W, _ = new.shape
    index = jnp.stack(jnp.broadcast_arrays(
        jnp.arange(B, dtype=jnp.int32)[:, None, None],
        jnp.arange(KV, dtype=jnp.int32)[None, :, None],
        positions.astype(jnp.int32)[:, None, :],
    ), axis=-1)  # [B, KV, W, 3]: (lane, KV head, position)
    # lax.scatter itself, not cache.at[..].set: jnp's index handling is
    # traced once per layer for K and for V in every burst variant
    # that warm() lowers, and cost ~0.2 s a variant there
    return lax.scatter(
        cache, index, new.astype(cache.dtype),
        lax.ScatterDimensionNumbers(
            update_window_dims=(3,), inserted_window_dims=(0, 1, 2),
            scatter_dims_to_operand_dims=(0, 1, 2)),
        mode=lax.GatherScatterMode.FILL_OR_DROP,
    )


def _windowed_kernel(starts_ref, *refs, **how):
    """``_ragged_kernel`` for a layer with a window: ``starts_ref`` (SMEM
    [B]) is the first position each lane sees."""
    _ragged_kernel(*refs, starts_ref=starts_ref, **how)


def _sink_kernel(sink_ref, *refs, **how):
    """``_ragged_kernel`` for a layer whose softmax has a sink:
    ``sink_ref`` (VMEM [KV, rep, 1] float32) is each query head's logit."""
    _ragged_kernel(*refs, sink_ref=sink_ref, **how)


def _packed_kernel(qrest_ref, *refs, inner, **how):
    """``inner`` (one of the three above) over a K array whose KV axis ends
    in packed rest rows: ``qrest_ref`` (VMEM [B, packed, pack x rep, Dh]) is
    the queries' rests, each laid in its head's part of a row."""
    inner(*refs, qrest_ref=qrest_ref, **how)


def _ragged_kernel(lens_ref, wpos_ref, q_ref, knew_ref, vnew_ref, _k_in, _v_in,
                   o_ref, k_hbm, v_hbm, kbuf, vbuf, kstage, vstage, sem, wsem,
                   rsem, *, block, starts_ref=None, rows=1, sink_ref=None,
                   qrest_ref=None):
    """The whole batch of one layer: for each lane with ``len > 0``, walk
    its ``ceil(len / block)`` blocks with an online softmax, and where a
    block holds the lane's ``write_pos`` put the new row into it first
    and copy the row's group back to the cache. A ``write_pos`` inside
    the cache that no block of the lane's read holds (no caller's: a step
    writes where its read ends) has its group fetched, patched and copied
    back after the lane's read, so the cache is the scatter's either way.

    lens_ref, wpos_ref: SMEM [B]; q_ref / o_ref: VMEM [B, KV, rep, Dh];
    knew_ref / vnew_ref: VMEM [B, KV, 1, Dh]; k_hbm / v_hbm: the layer's
    cache [B, KV, T, Dh], left where it is (the call's aliased outputs:
    ``_k_in`` / ``_v_in`` are the same buffers); kbuf / vbuf: VMEM [2, KV,
    block, Dh]; kstage / vstage: VMEM [B, KV, GROUP, Dh], a lane's patched
    group while its copy to the cache is in flight; sem: DMA semaphores
    [2 (k, v), 2] of the reads, wsem: [2 (k, v)] of the writes, rsem: [2
    (k, v)] of the fetch of a group that no block held.

    ``rows`` > 1, a window of that many positions a lane (``GROUP`` is a
    multiple of it, ``write_pos`` of the window's first a multiple of
    ``rows``, so the window lies in one group): knew_ref / vnew_ref are
    [B, KV, GROUP, Dh], the window's rows laid where they go in their
    group, and the queries' ``rep`` counts every row of the window: all
    of them see the same keys, the lane's ``len`` with the window in it.

    A value row may be narrower than a key row (v_hbm [B, KV, T, Dv], o_ref
    [B, KV, rep, Dv]). ``sink_ref``: a logit a query head that joins the
    lane's softmax after its last block and has no value row.

    Keys wider than a row (``packed_key_rows``): k_hbm, knew_ref, kbuf and
    kstage are [., KV + packed, ., Dh], the ``packed`` rows after the heads'
    own each holding the rests of ``pack = KV / packed`` heads, and
    ``qrest_ref`` [B, packed, pack x rep, Dh] the queries' rests, head
    ``j x pack + p``'s in columns ``[p x Dh / pack, (p + 1) x Dh / pack)``
    of its ``rep`` rows and zeros in the others: a head's score is its
    part's product plus its row of the packed one's. The copies, the write
    and the staging move whole rows of the KV axis and know nothing of it;
    only the scores and their scale (the key's own width) do.
    """
    n_lanes, n_kv, rep, dh = q_ref.shape
    dv = v_hbm.shape[3]
    t = k_hbm.shape[2]
    packed = k_hbm.shape[1] - n_kv
    scale = 1.0 / np.sqrt(_key_width(dh, n_kv, packed))

    def copies(lane, i, slot):
        start = pl.multiple_of(i * block, block)
        return (
            pltpu.make_async_copy(
                k_hbm.at[lane, :, pl.ds(start, block), :], kbuf.at[slot],
                sem.at[0, slot]),
            pltpu.make_async_copy(
                v_hbm.at[lane, :, pl.ds(start, block), :], vbuf.at[slot],
                sem.at[1, slot]),
        )

    def start(lane, i, slot):
        for c in copies(lane, i, slot):
            c.start()

    def write_back(which, lane, wp):
        stage, hbm = ((kstage, k_hbm), (vstage, v_hbm))[which]
        group = pl.multiple_of(wp // GROUP * GROUP, GROUP)
        return pltpu.make_async_copy(
            stage.at[lane], hbm.at[lane, :, pl.ds(group, GROUP), :],
            wsem.at[which])

    def patched(which, lane, wp, group):
        """``group`` [KV, GROUP, Dh] with the lane's new row at ``wp``."""
        row = lax.broadcasted_iota(jnp.int32, group.shape, 1)
        if rows > 1:
            at = wp % GROUP
            return jnp.where((row >= at) & (row < at + rows),
                             (knew_ref, vnew_ref)[which][lane], group)
        return jnp.where(
            row == wp % GROUP, (knew_ref, vnew_ref)[which][lane], group)

    def land(which, lane, wp, slot):
        """The block in ``slot`` holds position ``wp`` and its read is
        done: the new row replaces the stale one there, and its group
        starts back to the cache (nobody waits for it before the end)."""
        buf, stage = ((kbuf, kstage), (vbuf, vstage))[which]
        at = pl.ds(pl.multiple_of(wp % block // GROUP * GROUP, GROUP), GROUP)
        group = patched(which, lane, wp, buf[slot, :, at, :])
        buf[slot, :, at, :] = group
        stage[lane] = group
        write_back(which, lane, wp).start()

    def land_unread(which, lane, wp):
        """No block of the lane's read held ``wp``: its group comes from
        the cache into the staging buffer, takes the row and goes back."""
        stage, hbm = ((kstage, k_hbm), (vstage, v_hbm))[which]
        group = pl.multiple_of(wp // GROUP * GROUP, GROUP)
        fetch = pltpu.make_async_copy(
            hbm.at[lane, :, pl.ds(group, GROUP), :], stage.at[lane],
            rsem.at[which])
        fetch.start()
        fetch.wait()
        stage[lane] = patched(which, lane, wp, stage[lane])
        write_back(which, lane, wp).start()

    def next_live(lane):
        """The first lane after ``lane`` that reads anything, or B."""
        return lax.while_loop(
            lambda b: (b < n_lanes)
            & (lens_ref[jnp.minimum(b, n_lanes - 1)] <= 0),
            lambda b: b + 1, lane + 1)

    windowed = starts_ref is not None

    def first_block(lane):
        """The block a lane's walk begins at."""
        if not windowed:
            return 0
        return starts_ref[jnp.minimum(lane, n_lanes - 1)] // block

    first = next_live(jnp.int32(-1))

    @pl.when(first < n_lanes)
    def _():
        start(first, first_block(first), 0)

    def lane_body(lane, carry):
        done_blocks, written = carry
        n = lens_ref[lane]
        n_blocks = (n + block - 1) // block
        wp = wpos_ref[lane]
        # a live lane writes unless its position is parked outside the
        # cache; the block that takes the write (counted from the lane's
        # first), or none (-1) where the position lies outside what the
        # lane reads
        writes = (n > 0) & (wp >= 0) & (wp < t)
        w_block = jnp.where(writes & (wp // block < n_blocks),
                            wp // block, -1)
        if windowed:
            b0 = first_block(lane)
            n_blocks = n_blocks - b0
            w_block = jnp.where(w_block >= b0, w_block - b0, -1)
        q = q_ref[lane]  # [KV, rep, Dh]

        def nth(i):
            """The cache block that is the ``i``-th of the lane's walk."""
            return b0 + i if windowed else i

        def block_body(i, carry):
            o, m, l = carry
            slot = (done_blocks + i) % 2

            # the copy after this one: this lane's next block, or the
            # next live lane's first (so a lane boundary costs no wait)
            @pl.when(i + 1 < n_blocks)
            def _():
                start(lane, nth(i + 1), 1 - slot)

            @pl.when(i + 1 == n_blocks)
            def _():
                nxt = next_live(lane)

                @pl.when(nxt < n_lanes)
                def _():
                    start(nxt, first_block(nxt), 1 - slot)

            k_copy, v_copy = copies(lane, nth(i), slot)
            k_copy.wait()

            @pl.when(i == w_block)
            def _():
                land(0, lane, wp, slot)

            s = jnp.einsum(
                "grd,gkd->grk", q, kbuf[slot, :n_kv],
                preferred_element_type=jnp.float32,
            )  # [KV, rep, block]
            if packed:
                s = s + jnp.einsum(
                    "jrd,jkd->jrk", qrest_ref[lane], kbuf[slot, n_kv:],
                    preferred_element_type=jnp.float32,
                ).reshape(s.shape)
            s = s * scale
            col = nth(i) * block + lax.broadcasted_iota(jnp.int32, s.shape, 2)
            # the lane's first position is live in its first block, so m is
            # finite from there on and a masked entry's exp underflows to 0
            seen = col < n
            if windowed:
                seen = seen & (col >= starts_ref[lane])
            s = jnp.where(seen, s, NEG_INF)
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

        o, m, l = lax.fori_loop(
            0, n_blocks, block_body,
            (jnp.zeros((n_kv, rep, dv), jnp.float32),
             jnp.full((n_kv, rep, 1), NEG_INF, jnp.float32),
             jnp.zeros((n_kv, rep, 1), jnp.float32)),
        )
        if sink_ref is not None:
            sink = sink_ref[...]
            m_all = jnp.maximum(m, sink)
            alpha = jnp.exp(m - m_all)
            o, l = o * alpha, l * alpha + jnp.exp(sink - m_all)
        # a lane of length 0 ran no block: o = 0, l = 0, zeros out
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


@functools.partial(jax.jit, static_argnames=("block", "interpret", "name"))
def ragged_decode_attention(q, k, v, lens, k_new, v_new, write_pos,
                            block: int = None, interpret: bool = False,
                            starts=None, sink=None, name: str = None,
                            q_rest=None):
    """Pallas ragged decode attention with the step's write inside it.
    q [B, H, 1, Dh]; k, v the layer's cache [B, KV, T, Dh], unsliced
    (``T`` must divide by ``block``: ``walk_block()``'s for the call's
    shapes where none is given); lens [B] int32, clamped to [0, T];
    k_new, v_new [B, KV, 1, Dh] this step's rows and write_pos [B] where
    they go. Returns ``(o, k, v)``: the caches are aliased in and out, so
    under a caller that donates them nothing but the rows' groups moves.

    Lane b first takes its new row at ``write_pos[b]``, then attends to
    positions [0, lens[b]): the result is the read of the cache
    ``cache_write()`` would have made, bit for bit. The row lands from
    the block that holds it, while the read has that block in VMEM (a
    step writes at ``lens[b] - 1``, in the read's last block); a
    ``write_pos[b]`` in a block the lane does not read costs a fetch of
    its group after the read, and one outside [0, T) is dropped as the
    scatter drops it. A lane with ``lens[b] == 0`` reads nothing, gives
    zeros and WRITES NOTHING: its row is the K and V of a token nobody
    sampled, at a position no read admits before the lane's next
    occupant overwrites it.

    ``starts`` ([B] int32, optional: a layer with a window): lane b
    attends to positions [starts[b], lens[b]) and copies only the blocks
    that hold them; ``starts[b] < lens[b]`` wherever ``lens[b] > 0``.

    The second entry, a block of ``W`` positions a lane (q [B, H, W, Dh],
    k_new, v_new [B, KV, W, Dh], ``W`` a divisor of ``GROUP``, no
    ``starts``): the rows land at ``write_pos[b] .. + W - 1``
    (``write_pos[b]`` a multiple of ``W``: the caller's to hold) and EVERY
    query of the block attends to [0, lens[b]), the block's own rows among
    them, which is attention that is open inside a block. The kernel is the
    same walk with ``W`` times the query rows a KV head; in a trace its
    name is ``block_decode_attention``.

    v may hold narrower rows than k ([B, KV, T, Dv], v_new [B, KV, 1, Dv]):
    the output is [B, H, 1, Dv] and the scale the keys'. ``sink`` ([H]
    float32, optional, one position a lane and no ``starts``): a logit a
    query head that joins the lane's softmax and has no value row. A
    cache that is a RING is this call as it is: ``T`` the ring's length,
    ``write_pos`` the position modulo it, ``lens`` at most ``T`` (keys
    carry their rotary, so a softmax over a ring needs no order, only the
    bound of the slots written). ``name``: the kernel's name in a trace,
    where a caller wants its own.

    Keys wider than a row, held packed (``packed_key_rows``, ``pack_keys``):
    k [B, KV + packed, T, Dh] and k_new [B, KV + packed, 1, Dh] with the
    heads' rests in the last ``packed`` rows of the KV axis (v's says how
    many heads there are), q the queries' first ``Dh`` dims and ``q_rest``
    [B, H, 1, Dk - Dh] the dims past them, as projected: the scores are
    ``(q . k + q_rest . k_rest) / sqrt(Dk)``, the read of the keys held
    whole. An iteration still issues two copies, K's 1 + 1 / (2 pack) rows a
    head where a row padded to ``2 Dh`` was 2."""
    b, h, t_q, dh = q.shape
    n_kv, t, dv = v.shape[1], k.shape[2], v.shape[3]
    packed = k.shape[1] - n_kv
    if (q_rest is None) != (packed == 0) or (packed and (
            n_kv % packed or dh % (n_kv // packed)
            or q_rest.shape != (b, h, t_q, dh * packed // n_kv))):
        raise ValueError(
            f"k {k.shape} beside v {v.shape}: {packed} packed rows want the "
            f"queries' rests [B, H, T, Dh x packed / KV], and no others do")
    if block is None:
        dk = _key_width(dh, n_kv, packed)
        block = walk_block(n_kv, dk, k.dtype, t, None if dv == dk else dv)
    if sink is not None and (t_q > 1 or starts is not None):
        raise ValueError("a sink: one position a lane and no starts")
    if GROUP % t_q or (t_q > 1 and starts is not None) or h % n_kv \
            or t % block or block % GROUP:
        raise ValueError(
            f"q {q.shape} / cache {k.shape} do not fit the kernel "
            f"(T == 1, or a divisor of {GROUP} and no starts; H a multiple "
            f"of KV, cache length a multiple of {block}, block a multiple "
            f"of {GROUP})"
        )
    rep = h // n_kv * t_q
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    kernel, scalars = _ragged_kernel, ()
    if starts is not None:
        kernel = _windowed_kernel
        scalars = (jnp.clip(starts.astype(jnp.int32), 0, t),)
    lead_specs = [smem] * len(scalars)
    if sink is not None:
        kernel = _sink_kernel
        scalars = (sink.astype(jnp.float32).reshape(n_kv, rep, 1),)
        lead_specs = [vmem]
    named = {} if name is None else {"name": name}
    if t_q > 1:
        # [B, KV, W, Dh] -> [B, KV, GROUP, Dh]: row r holds the window's
        # row r mod W, so the rows lie where they go wherever in its group
        # the window starts
        k_new, v_new = (jnp.tile(new, (1, 1, GROUP // t_q, 1))
                        for new in (k_new, v_new))
        kernel = functools.partial(kernel, rows=t_q)
        named = {"name": "block_decode_attention"}
    if packed:
        # [B, H, T, r] -> [B, packed, pack x rep, pack x r]: head j x pack +
        # p's rows hold its rest in the p-th part of the row, zeros beside it
        pack = n_kv // packed
        r = dh // pack
        rests = q_rest.reshape(b, packed, pack, rep, r)
        laid = jnp.concatenate([
            jnp.pad(rests[:, :, p], ((0, 0),) * 3 + ((p * r, dh - (p + 1) * r),))
            for p in range(pack)], axis=2)
        kernel = functools.partial(_packed_kernel, inner=kernel)
        scalars = (laid, *scalars)
        lead_specs = [vmem] + lead_specs
    out, k, v = pl.pallas_call(
        functools.partial(kernel, block=block),
        out_shape=(
            jax.ShapeDtypeStruct((b, n_kv, rep, dv), q.dtype),
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ),
        in_specs=lead_specs + [smem, smem, vmem, vmem, vmem, hbm, hbm],
        out_specs=(vmem, hbm, hbm),
        input_output_aliases={len(scalars) + 5: 1, len(scalars) + 6: 2},
        scratch_shapes=[
            pltpu.VMEM((2, k.shape[1], block, dh), k.dtype),
            pltpu.VMEM((2, n_kv, block, dv), v.dtype),
            pltpu.VMEM((b, k.shape[1], GROUP, dh), k.dtype),
            pltpu.VMEM((b, n_kv, GROUP, dv), v.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        interpret=interpret,
        **named,
    )(*scalars,
      jnp.clip(lens.astype(jnp.int32), 0, t), write_pos.astype(jnp.int32),
      q.reshape(b, n_kv, rep, dh), k_new.astype(k.dtype),
      v_new.astype(v.dtype), k, v)
    return out.reshape(b, h, t_q, dv), k, v


@functools.partial(jax.jit, static_argnames=("attn_len", "mesh", "name"))
def decode_attention(q, k, v, k_new, v_new, write_pos, pos, lens,
                     attn_len=None, mesh=None, starts=None, sink=None,
                     name: str = None, q_rest=None):
    """The decode step's write and read of one layer's cache: this step's
    rows k_new, v_new [B, KV, 1, Dh] go into the UNSLICED cache k, v [B,
    KV, T, Dh] at ``write_pos`` [B] (outside [0, T): dropped), then q [B,
    H, 1, Dh] attends to it. Returns ``(o, k, v)``. ``pos`` [B] is each
    lane's position and ``lens`` [B] what is read of its cache: ``pos +
    1`` for a lane whose output anyone reads, 0 for one that is idle or
    done. ``attn_len`` (static) is the scheduler's bucket, an upper bound
    on every ``lens[b]``; None where the caller has none, and the bound
    is then the cache's length.

    The kernel streams ``lens[b]`` positions of lane b and lands the
    lane's row from the block that holds it (wherever in the cache
    ``write_pos[b]`` lies); a lane with ``lens[b] == 0`` is given zeros
    and its row is NOT written: no read admits that position before the
    lane's next occupant overwrites it. The scatter and the dots cannot
    skip a lane: they write every lane's row and read ``attn_len``
    positions of every lane under the ``key_pos <= pos`` mask, as they
    did before there was a kernel, so an idle lane's output is there what
    its stale position makes it. Nobody reads that output; where
    ``lens[b] > 0`` the two agree to rounding, caches bit for bit
    (tests/test_decode_attention.py).

    Jitted, so the burst's unrolled layers lower it once and call it
    (24 call sites a step would otherwise trace and lower 24 kernels in
    every variant ``warm()`` builds).

    ``mesh``: the serving mesh when the caller runs under one
    (``reads_ragged()``: it takes the scatter and the dots).

    ``starts`` ([B], optional): the first position each lane sees, for a
    layer with a window (``max(0, lens - window)``); the kernel then
    copies only the blocks from there on, and the dots take the same
    band as a mask. None: every lane sees from 0.

    Keys wider than values (k [B, KV, T, Dk], v [B, KV, T, Dv]), ``sink``
    ([H] float32: a logit a query head in the softmax, with no value row)
    and ``name`` (the kernel's name in a trace): as
    ``ragged_decode_attention()`` takes them (a sink and no ``starts``),
    the ring among them. ONE choice between the kernel and the dots for
    every caller: ``starts``, ``sink`` or ``q_rest`` is an operand of both
    where given and a Python ``None`` where not, which no trace sees.

    Keys held packed (``packed_key_rows``): k [B, KV + packed, T, Dh] with
    v [B, KV, T, Dv], k_new as ``pack_keys`` lays it, q the queries' first
    ``Dh`` dims and ``q_rest`` the rest. The dots write the same rows and
    read the keys put back whole (``unpack_keys``: a copy of what they
    read, on the platforms that take them).
    """
    if sink is not None and starts is not None:
        raise ValueError("a sink: one position a lane and no starts")
    t = k.shape[2]
    packed = k.shape[1] - v.shape[1]
    bound = t if attn_len is None else min(int(attn_len), t)
    given = {n: a for n, a in (("starts", starts), ("sink", sink),
                               ("q_rest", q_rest)) if a is not None}

    def dots(q, k, v, k_new, v_new, write_pos, pos, lens, *more):
        more = dict(zip(given, more))
        k = cache_write(k, k_new, write_pos[:, None])
        v = cache_write(v, v_new, write_pos[:, None])
        keys = lax.slice_in_dim(k, 0, bound, axis=2)
        if packed:
            q = jnp.concatenate([q, more["q_rest"]], axis=-1)
            keys = unpack_keys(keys, packed)
        o = cache_attention(
            q, keys, lax.slice_in_dim(v, 0, bound, axis=2), pos, q.dtype,
            lo=more.get("starts"), sink=more.get("sink"))
        return o, k, v

    def kernel(q, k, v, k_new, v_new, write_pos, pos, lens, *more):
        return ragged_decode_attention(
            q, k, v, jnp.minimum(lens, bound), k_new, v_new, write_pos,
            name=name, **dict(zip(given, more)))

    args = (q, k, v, k_new, v_new, write_pos, pos, lens, *given.values())
    dv = v.shape[3]
    # the platform is known only when this is lowered: ask whether a
    # lowering for a TPU takes the kernel, and let that lowering choose
    if not reads_ragged(
            "tpu", q.shape, k.shape, (q.dtype, k.dtype, v.dtype), mesh,
            None if dv == k.shape[3] else dv, packed):
        return dots(*args)
    return lax.platform_dependent(*args, tpu=kernel, default=dots)


def block_reads_ragged(platform, q_shape, cache_shape, dtypes, mesh=None,
                       v_dim=None, packed=0) -> bool:
    """``reads_ragged()`` for ``block_decode_attention()``: the kernel's
    second entry takes a block of ``W = q_shape[2]`` positions a lane where
    ``W`` divides ``GROUP`` (the block then lies in one group of the cache's
    rows) and everything else is as the single position's: a head size
    that fills lanes, whole GQA groups, whole blocks of the walk
    (``walk_block()``) and one dtype, and no serving mesh. ``v_dim`` (a
    value row's width where it is not the key row's) fills lanes too:
    Mosaic slices no row of 192 ("must be aligned to tiling (128)"; such a
    row occupies 256 in HBM either way), so a family with such keys cuts
    them at 128 and packs the rests (``packed_key_rows``: ``packed`` rows
    of ``cache_shape``'s KV axis, ``q_shape`` the queries' 128-wide part)
    or, where its shapes do not divide so, holds them in rows of 256, zero
    past the key."""
    _, h, w, dh = q_shape
    n_kv, t = cache_shape[1] - packed, cache_shape[2]
    return (
        platform == "tpu"
        and mesh is None
        and GROUP % w == 0
        and dh % LANES == 0 and (v_dim or dh) % LANES == 0
        and h % n_kv == 0
        and t % walk_block(n_kv, _key_width(dh, n_kv, packed), dtypes[1], t,
                           v_dim) == 0
        and len(set(dtypes)) == 1
    )


@functools.partial(jax.jit, static_argnames=("attn_len", "mesh"))
def block_decode_attention(q, k, v, k_new, v_new, base, lens, attn_len=None,
                           mesh=None):
    """A block of ``W`` positions a lane over one layer's cache, attention
    open inside the block: the block's rows k_new, v_new [B, KV, W, Dh] go
    into the UNSLICED cache k, v [B, KV, T, Dh] at ``base[b] .. + W - 1``
    (``base`` [B], each a multiple of ``W``; a later pass over the same
    block overwrites them), then every query of q [B, H, W, Dh] attends to
    positions [0, base[b] + W). Returns ``(o, k, v)``. ``lens`` [B]:
    ``base + W`` for a live lane, 0 for one that is idle or done: it reads
    nothing, is given what nobody reads and WRITES NOTHING, under the
    kernel and under the dots alike. ``attn_len`` / ``mesh``: as
    ``decode_attention()`` takes them.

    Under ``jax.named_scope("block_decode_attention")``, the kernel's own
    name in a trace."""
    t = k.shape[2]
    w = q.shape[2]
    bound = t if attn_len is None else min(int(attn_len), t)

    def dots(q, k, v, k_new, v_new, base, lens):
        at = jnp.where(lens[:, None] > 0,
                       base[:, None] + jnp.arange(w, dtype=jnp.int32), t)
        k = cache_write(k, k_new, at)
        v = cache_write(v, v_new, at)
        o = cache_attention(
            q, lax.slice_in_dim(k, 0, bound, axis=2),
            lax.slice_in_dim(v, 0, bound, axis=2),
            jnp.broadcast_to(base[:, None] + (w - 1), (q.shape[0], w)),
            q.dtype)
        return o, k, v

    def kernel(q, k, v, k_new, v_new, base, lens):
        return ragged_decode_attention(
            q, k, v, jnp.minimum(lens, bound), k_new, v_new, base)

    args = (q, k, v, k_new, v_new, base.astype(jnp.int32),
            lens.astype(jnp.int32))
    with jax.named_scope("block_decode_attention"):
        if not block_reads_ragged(
                "tpu", q.shape, k.shape, (q.dtype, k.dtype, v.dtype), mesh):
            return dots(*args)
        return lax.platform_dependent(*args, tpu=kernel, default=dots)
