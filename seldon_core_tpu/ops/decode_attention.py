"""Single-position decode attention over the KV cache: a ragged Pallas TPU
kernel that streams each lane's own length, and the two XLA dots.

Motivation: a decode burst reads the cache through one static bucket
(``attn_len`` >= the deepest lane's end-of-burst position), so the dots
read every lane's whole bucket: idle lanes and the tail past a lane's
position included. On a part-loaded replica that is 90-97% of the KV
bytes (ISSUE 30). The kernel takes ``lens [B]`` and copies
``ceil(len / block)`` blocks of K and of V per lane from HBM; a lane of
length 0 issues no copy.

The earlier kernel that lost here (23.7 ms a step against the dots' 6.0,
16 lanes x 1920 keys) had a grid of (lanes, chunks) programs with
``[block_k, Dh]`` chunks per head: program overhead x (layers x lanes x
chunks). This one is ONE program per layer: the walk over lanes and
over a lane's blocks are loops inside it, a block is ``block`` positions
x all KV heads (512 KB a K and V pair), double-buffered across lane
boundaries, so the next lane's first block is in flight while this
lane's last one is computed.

``decode_attention()`` is the public entry: it picks the kernel by what
it can see (``T == 1``, ``head_dim`` a multiple of 128, the cache length
a multiple of the block, no serving mesh) and by the platform the
executable is LOWERED for (``lax.platform_dependent``; a process whose
backend is the CPU can compile for a described TPU and gets the
kernel), and takes ``cache_attention()``'s dots everywhere else. That
rule is ``reads_ragged()``; the scheduler asks it too, because a read
that bounds itself per lane needs no static bucket and so no executable
per bucket. ``ragged_decode_attention()`` is the kernel itself
(``interpret`` runs it on the CPU for the equivalence tests).
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
# Positions per copy: K and V blocks of [KV, BLOCK, Dh] each (256 KB each
# at 8 KV heads of 128 in bfloat16). In the burst on a v5e, ms a step at
# 128 / 256 / 512 (my chip runs, PR 30): 8 of 28 lanes live under a 1280
# bucket 7.52 / 7.47 / 7.60, 28 of 28 at 64-560 under 640: 8.43 / 8.66 /
# 8.81, Mistral 32 of 32: 12.24 / 12.37 / 12.63, one lane of 32 at 1700:
# 11.42 all three. A lane's length rounds up to the block, so the smaller
# block reads less, and its extra iterations cost less than that saves.
# It is also the scheduler's bucket step (``attn_bucket``), so every
# bucket tiles.
BLOCK = 128


def reads_ragged(platform, q_shape, cache_shape, dtypes, mesh=None) -> bool:
    """Whether ``decode_attention()``, lowered for ``platform``, reads
    each lane's own length (the kernel) and not a static bound of every
    lane (the dots). ``q_shape`` [B, H, T, Dh]; ``cache_shape`` [B, KV,
    Tc, Dh] of one layer's K (V's is the same); ``dtypes`` of q, K and V.

    The kernel wants one query position, a head size that fills lanes,
    whole GQA groups, whole blocks and one dtype; Mosaic kernels cannot
    be partitioned by GSPMD, so a serving mesh takes the dots."""
    return (
        platform == "tpu"
        and mesh is None
        and q_shape[2] == 1
        and q_shape[-1] % 128 == 0
        and q_shape[1] % cache_shape[1] == 0
        and cache_shape[2] % BLOCK == 0
        and len(set(dtypes)) == 1
    )


def cache_attention(q, kc, vc, bound, dt):
    """Attention over the (sliced) KV cache with a key_pos <= bound
    mask, WITHOUT materialising a head-repeated cache copy.

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
    """
    B, Hl, T, Dh = q.shape
    KVl, Ta = kc.shape[1], kc.shape[2]
    rep = Hl // KVl
    key_pos = jnp.arange(Ta, dtype=jnp.int32)
    if getattr(bound, "ndim", 0) == 2:  # [B, T]
        mask = key_pos[None, None, None, None, :] <= bound[:, None, None, :, None]
    else:  # [B]
        mask = key_pos[None, None, None, None, :] <= bound[:, None, None, None, None]
    qg = q.reshape(B, KVl, rep, T, Dh)
    s = lax.dot_general(
        qg, kc, (((4,), (3,)), ((0, 1), (0, 1))),
        preferred_element_type=jnp.float32,
    ) / np.sqrt(Dh)  # [B, KV, rep, T, Ta]
    s = jnp.where(mask, s, NEG_INF)
    w = jax.nn.softmax(s, -1).astype(dt)
    o = lax.dot_general(
        w, vc, (((4,), (2,)), ((0, 1), (0, 1))),
        preferred_element_type=jnp.float32,
    ).astype(dt)  # [B, KV, rep, T, Dh]
    return o.reshape(B, Hl, T, Dh)


def _ragged_kernel(lens_ref, q_ref, k_hbm, v_hbm, o_ref, kbuf, vbuf, sem,
                   *, block):
    """The whole batch of one layer: for each lane with ``len > 0``, walk
    its ``ceil(len / block)`` blocks with an online softmax.

    lens_ref: SMEM [B]; q_ref / o_ref: VMEM [B, KV, rep, Dh]; k_hbm /
    v_hbm: the layer's cache [B, KV, T, Dh], left where it is; kbuf /
    vbuf: VMEM [2, KV, block, Dh]; sem: DMA semaphores [2 (k, v), 2].
    """
    n_lanes, n_kv, rep, dh = q_ref.shape
    scale = 1.0 / np.sqrt(dh)

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

    def next_live(lane):
        """The first lane after ``lane`` that reads anything, or B."""
        return lax.while_loop(
            lambda b: (b < n_lanes)
            & (lens_ref[jnp.minimum(b, n_lanes - 1)] <= 0),
            lambda b: b + 1, lane + 1)

    first = next_live(jnp.int32(-1))

    @pl.when(first < n_lanes)
    def _():
        start(first, 0, 0)

    def lane_body(lane, done_blocks):
        n = lens_ref[lane]
        n_blocks = (n + block - 1) // block
        q = q_ref[lane]  # [KV, rep, Dh]

        def block_body(i, carry):
            o, m, l = carry
            slot = (done_blocks + i) % 2

            # the copy after this one: this lane's next block, or the
            # next live lane's first (so a lane boundary costs no wait)
            @pl.when(i + 1 < n_blocks)
            def _():
                start(lane, i + 1, 1 - slot)

            @pl.when(i + 1 == n_blocks)
            def _():
                nxt = next_live(lane)

                @pl.when(nxt < n_lanes)
                def _():
                    start(nxt, 0, 1 - slot)

            k_copy, v_copy = copies(lane, i, slot)
            k_copy.wait()
            s = jnp.einsum(
                "grd,gkd->grk", q, kbuf[slot],
                preferred_element_type=jnp.float32,
            ) * scale  # [KV, rep, block]
            col = i * block + lax.broadcasted_iota(jnp.int32, s.shape, 2)
            # position 0 is live in a lane's first block, so m is finite
            # from there on and a masked entry's exp underflows to 0
            s = jnp.where(col < n, s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new)
            l = l * alpha + p.sum(axis=-1, keepdims=True)
            v_copy.wait()
            o = o * alpha + jnp.einsum(
                "grk,gkd->grd", p.astype(vbuf.dtype), vbuf[slot],
                preferred_element_type=jnp.float32,
            )
            return o, m_new, l

        o, _, l = lax.fori_loop(
            0, n_blocks, block_body,
            (jnp.zeros((n_kv, rep, dh), jnp.float32),
             jnp.full((n_kv, rep, 1), NEG_INF, jnp.float32),
             jnp.zeros((n_kv, rep, 1), jnp.float32)),
        )
        # a lane of length 0 ran no block: o = 0, l = 0, zeros out
        o_ref[lane] = (o / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)
        return done_blocks + n_blocks

    lax.fori_loop(0, n_lanes, lane_body, jnp.int32(0))


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def ragged_decode_attention(q, k, v, lens, block: int = BLOCK,
                            interpret: bool = False):
    """Pallas ragged decode attention. q [B, H, 1, Dh]; k, v the layer's
    cache [B, KV, T, Dh], unsliced (``T`` must divide by ``block``);
    lens [B] int32, clamped to [0, T]. Lane b attends to positions
    [0, lens[b]); 0 reads nothing and gives zeros."""
    b, h, t_q, dh = q.shape
    n_kv, t = k.shape[1], k.shape[2]
    if t_q != 1 or h % n_kv or t % block:
        raise ValueError(
            f"q {q.shape} / cache {k.shape} do not fit the kernel "
            f"(T == 1, H a multiple of KV, cache length a multiple of {block})"
        )
    rep = h // n_kv
    out = pl.pallas_call(
        functools.partial(_ragged_kernel, block=block),
        out_shape=jax.ShapeDtypeStruct((b, n_kv, rep, dh), q.dtype),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((2, n_kv, block, dh), k.dtype),
            pltpu.VMEM((2, n_kv, block, dh), v.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
        ],
        interpret=interpret,
    )(jnp.clip(lens.astype(jnp.int32), 0, t), q.reshape(b, n_kv, rep, dh), k, v)
    return out.reshape(b, h, 1, dh)


@functools.partial(jax.jit, static_argnames=("attn_len", "mesh"))
def decode_attention(q, k, v, pos, lens, attn_len=None, mesh=None):
    """Dispatching decode attention: q [B, H, 1, Dh] against the layer's
    UNSLICED cache k, v [B, KV, T, Dh]. ``pos`` [B] is each lane's
    position and ``lens`` [B] what is read of its cache: ``pos + 1`` for
    a lane whose output anyone reads, 0 for one that is idle or done.
    ``attn_len`` (static) is the scheduler's bucket, an upper bound on
    every ``lens[b]``; None where the caller has none, and the bound is
    then the cache's length.

    The kernel streams ``lens[b]`` positions of lane b, none for 0, and
    gives such a lane zeros. The dots cannot skip a lane: they read
    ``attn_len`` positions of every lane under the ``key_pos <= pos``
    mask, as they did before there was a kernel, so an idle lane's row is
    there what its stale position makes it. Nobody reads that row; where
    ``lens[b] > 0`` the two agree to rounding (tests/test_decode_attention.py).

    Jitted, so the burst's unrolled layers lower it once and call it
    (24 call sites a step would otherwise trace and lower 24 kernels in
    every variant ``warm()`` builds).

    ``mesh``: the serving mesh when the caller runs under one
    (``reads_ragged()``: it takes the dots).
    """
    t = k.shape[2]
    bound = t if attn_len is None else min(int(attn_len), t)

    def dots(q, k, v, pos, lens):
        return cache_attention(
            q, lax.slice_in_dim(k, 0, bound, axis=2),
            lax.slice_in_dim(v, 0, bound, axis=2), pos, q.dtype)

    def kernel(q, k, v, pos, lens):
        return ragged_decode_attention(
            q, k, v, jnp.minimum(lens, bound), block=BLOCK)

    # the platform is known only when this is lowered: ask whether a
    # lowering for a TPU takes the kernel, and let that lowering choose
    if not reads_ragged(
            "tpu", q.shape, k.shape, (q.dtype, k.dtype, v.dtype), mesh):
        return dots(q, k, v, pos, lens)
    return lax.platform_dependent(
        q, k, v, pos, lens, tpu=kernel, default=dots)
