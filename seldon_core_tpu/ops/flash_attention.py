"""Blocked (flash) attention as a Pallas TPU kernel.

Motivation: prefill attention materialises the full [T, T] score matrix
in XLA — at long prompts that is O(T^2) HBM traffic and VMEM spill. The
flash kernel streams K/V blocks through VMEM with an online-softmax
accumulator, so scores never leave VMEM and HBM traffic is O(T * Dh).
No reference counterpart (the reference ships no kernels at all); the
algorithm is the standard FlashAttention blocking, tiled for the MXU
(128-row blocks, f32 accumulators, bf16 operands).

``attention()`` is the public entry: it dispatches to the Pallas kernel
on TPU for shapes that tile cleanly and takes the XLA einsum path
(parallel/ring.full_attention's math) everywhere else — CPU tests,
tiny prompts, ragged head dims. ``flash_attention()`` is the kernel
itself (its ``interpret`` flag runs it on CPU for equivalence tests).

Used by DecoderLM.prefill (serving prefill is inference-only, so the
kernel needs no VJP). The BERT encoder keeps its XLA attention: its
per-row padding bias doesn't fit the kernel's mask model, and at seq 128
XLA is already at the compute roof.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.sharding import PartitionSpec as P

NEG_INF = -1e30


def _xla_attention(q, k, v, causal: bool, kv_len=None, window=None):
    """Reference attention, same contract as the kernel — delegates to
    parallel/ring.full_attention so the fallback and the trained/ring
    paths share ONE copy of the math. ``window``: the kernel's band, as
    the same masked dots."""
    from ..parallel.ring import full_attention

    if window is None:
        return full_attention(q, k, v, causal=causal, kv_len=kv_len)
    if not causal or kv_len is not None:
        raise ValueError("a window is causal and takes no kv_len")
    return _banded_attention(q, k, v, window)


def _banded_attention(q, k, v, window: int):
    """Causal attention in which query i sees key j iff i - window < j <= i:
    full_attention's math under the band's mask."""
    scale = 1.0 / (q.shape[-1] ** 0.5)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    row = jnp.arange(q.shape[2])[:, None]
    col = jnp.arange(k.shape[2])[None, :]
    s = jnp.where(((row >= col) & (col > row - window))[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)).astype(q.dtype)


def _prefixed_attention(q, k, v, prefix: int, prefix_len):
    """Causal attention behind a visible prefix: keys and values [B, H,
    prefix + T, D] whose first ``prefix`` rows are a prefix of which every
    query sees the first ``prefix_len`` (traced) and whose other T rows are
    the queries' own positions, seen causally. ONE softmax over both; the
    kernel's mask as masked dots."""
    scale = 1.0 / (q.shape[-1] ** 0.5)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    row = jnp.arange(q.shape[2])[:, None]
    col = jnp.arange(k.shape[2])[None, :]
    seen = jnp.where(col < prefix, col < prefix_len, row >= col - prefix)
    s = jnp.where(seen[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)).astype(q.dtype)


def _prefixed_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, *, block_q,
                     block_k, prefix):
    """``_flash_kernel`` behind a visible prefix (``len_ref``: SMEM [1],
    scalar-prefetched): the first ``prefix`` keys are seen by every row
    where they lie below ``len_ref[0]``, the others causally; the walk
    takes the prefix's ``ceil(len / block_k)`` blocks and then the causal
    ones, and a prefix block past the visible ones is never read."""
    _flash_kernel(q_ref, k_ref, v_ref, o_ref, block_q=block_q,
                  block_k=block_k, causal=True, prefix=prefix,
                  prefix_len=len_ref[0])


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *, block_q, block_k, causal,
                  window=None, prefix=None, prefix_len=None):
    """One (bh, q-block) program: stream K/V blocks with online softmax.

    q_ref: [1, block_q, Dh]; k_ref: [1, Tk, Dh]; v_ref: [1, Tk, Dv] and
    o_ref: [1, block_q, Dv] (whole keys for this bh resident in VMEM —
    serving-sized Tk*Dh fits easily). Dv is Dh but where a family's keys
    are wider than its values (latent attention: 192 and 128).
    ``window`` (static, with ``causal``): row i sees columns (i - window,
    i]; the walk starts at the block that holds the q-block's first row's
    first column and key blocks wholly left of the band are never read.
    ``prefix`` (static, a multiple of ``block_k``, with ``causal``) and
    ``prefix_len`` (traced): ``_prefixed_kernel``'s.
    """
    qb = pl.program_id(1)
    dh = q_ref.shape[-1]
    scale = 1.0 / np.sqrt(dh)
    q = q_ref[0].astype(jnp.float32) * scale  # [block_q, Dh]
    t_k = k_ref.shape[1]
    row = qb * block_q + lax.broadcasted_iota(jnp.int32, (block_q, 1), 0)

    def body(i, carry, in_prefix=False):
        o, m, l = carry
        # the traced start is block-aligned: say so, or Mosaic cannot
        # prove the sublane slice lands on a tile edge
        start = pl.multiple_of(i * block_k, block_k)
        kb = k_ref[0, pl.ds(start, block_k), :].astype(jnp.float32)
        vb = v_ref[0, pl.ds(start, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, kb, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [block_q, block_k]
        if causal:
            col = i * block_k + lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
            if in_prefix:
                seen = col < prefix_len
            elif prefix is not None:
                seen = row >= col - prefix
            else:
                seen = row >= col
            if window is not None:
                seen = seen & (col > row - window)
            s = jnp.where(seen, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        # Masked entries hold NEG_INF (finite -1e30): under this kernel's
        # causal dispatch every row admits column 0, so m_new is finite
        # after the first k-block and exp(NEG_INF - m_new) underflows to
        # exactly 0 — no NaN, no select needed in the hot loop. A mask
        # that fully hides a row would leave m_new == NEG_INF and make
        # p == 1 per entry (an unweighted mean of V, not zeros); reuse
        # with such masks requires a p = where(s == NEG_INF, 0, ...) guard.
        p = jnp.exp(s - m_new)
        if window is not None or in_prefix:
            # the band hides the walk's first block from the q-block's
            # later rows whole (and a prefix block its invisible tail from
            # every row, before any row has a finite m): the guard the note
            # above asks for
            p = jnp.where(seen, p, 0.0)
        l = l * alpha + p.sum(axis=-1, keepdims=True)
        o = o * alpha + jax.lax.dot_general(
            p, vb, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        return o, m_new, l

    n_k = t_k // block_k
    if causal:
        # blocks fully above the diagonal contribute nothing: stop at the
        # q-block's last row (block sizes are equal-or-multiples, so the
        # bound lands on a block edge or inside the masked block)
        n_k = jnp.minimum(n_k, (qb * block_q + block_q + block_k - 1) // block_k)
    o = jnp.zeros((block_q, v_ref.shape[-1]), jnp.float32)
    m = jnp.full((block_q, 1), NEG_INF, jnp.float32)
    l = jnp.zeros((block_q, 1), jnp.float32)
    first = 0
    if window is not None:
        first = jnp.maximum(0, (qb * block_q - window + 1) // block_k)
    if prefix is not None:
        # the visible prefix first, then from the first causal block to the
        # q-block's last row
        o, m, l = lax.fori_loop(
            0, (prefix_len + block_k - 1) // block_k,
            functools.partial(body, in_prefix=True), (o, m, l))
        first = prefix // block_k
        n_k = jnp.minimum(
            t_k // block_k,
            first + (qb * block_q + block_q + block_k - 1) // block_k)
    o, m, l = lax.fori_loop(first, n_k, body, (o, m, l))
    # l == 0 is unreachable via the causal equal-block dispatch (see the
    # loop-body comment); kept as a belt against 0/0 if the kernel is
    # rebuilt with a row-hiding mask
    l = jnp.where(l == 0.0, 1.0, l)
    o_ref[0] = (o / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "block_q", "block_k", "interpret", "window",
                     "name", "prefix"),
)
def flash_attention(
    q,
    k,
    v,
    causal: bool = True,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
    window=None,
    name=None,
    prefix=None,
    prefix_len=None,
):
    """Pallas blocked attention. q [B,H,Tq,Dh], k [B,H,Tk,Dh], v
    [B,H,Tk,Dv] (Dv = Dh everywhere but latent attention's prefill).
    Tq must divide by block_q and Tk by block_k (use :func:`attention`
    for the dispatching fallback). ``window`` (static int, causal only):
    query i sees keys (i - window, i]. ``name``: the kernel's name in a
    trace, where a caller wants its own. ``prefix`` (static int, causal
    only, a multiple of ``block_k``) with ``prefix_len`` (a traced int32
    scalar): k and v are [B, H, prefix + Tq, .], their first ``prefix`` rows
    a prefix every query sees the first ``prefix_len`` rows of, the others
    the queries' own positions."""
    if window is not None and not causal:
        raise ValueError("a window is causal")
    b, h, t_q, dh = q.shape
    t_k = k.shape[2]
    if prefix is not None:
        if not causal or window is not None or prefix % block_k \
                or t_k != prefix + t_q:
            raise ValueError(
                f"a prefix of {prefix} before {t_q} causal keys: Tk={t_k}, "
                f"block {block_k}, no window")
        return _prefixed_flash(q, k, v, prefix, prefix_len, block_q, block_k,
                               interpret, name)
    if t_q % block_q or t_k % block_k:
        raise ValueError(
            f"Tq={t_q} / Tk={t_k} must tile by block ({block_q}, {block_k})"
        )
    qf = q.reshape(b * h, t_q, dh)
    kf = k.reshape(b * h, t_k, dh)
    dv = v.shape[-1]
    vf = v.reshape(b * h, t_k, dv)
    kernel = functools.partial(
        _flash_kernel,
        block_q=block_q,
        block_k=block_k,
        causal=causal,
    )
    if window is not None:
        kernel = functools.partial(kernel, window=int(window))
    out = pl.pallas_call(
        kernel,
        # under shard_map the result varies over the mesh axes q does
        out_shape=jax.ShapeDtypeStruct(
            (b * h, t_q, dv), q.dtype, vma=jax.typeof(q).vma
        ),
        grid=(b * h, t_q // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, dh), lambda bh, i: (bh, i, 0)),
            pl.BlockSpec((1, t_k, dh), lambda bh, i: (bh, 0, 0)),
            pl.BlockSpec((1, t_k, dv), lambda bh, i: (bh, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, dv), lambda bh, i: (bh, i, 0)),
        interpret=interpret,
        **({} if name is None else {"name": name}),
    )(qf, kf, vf)
    return out.reshape(b, h, t_q, dv)


def _prefixed_flash(q, k, v, prefix, prefix_len, block_q, block_k,
                    interpret, name):
    """``flash_attention``'s call with the visible length prefetched into
    SMEM (a grid of its own: the other call's is as it was)."""
    from jax.experimental.pallas import tpu as pltpu

    b, h, t_q, dh = q.shape
    t_k, dv = k.shape[2], v.shape[-1]
    if t_q % block_q:
        raise ValueError(f"Tq={t_q} must tile by block {block_q}")
    out = pl.pallas_call(
        functools.partial(_prefixed_kernel, block_q=block_q, block_k=block_k,
                          prefix=int(prefix)),
        out_shape=jax.ShapeDtypeStruct((b * h, t_q, dv), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b * h, t_q // block_q),
            in_specs=[
                pl.BlockSpec((1, block_q, dh), lambda bh, i, n: (bh, i, 0)),
                pl.BlockSpec((1, t_k, dh), lambda bh, i, n: (bh, 0, 0)),
                pl.BlockSpec((1, t_k, dv), lambda bh, i, n: (bh, 0, 0)),
            ],
            out_specs=pl.BlockSpec(
                (1, block_q, dv), lambda bh, i, n: (bh, i, 0)),
        ),
        interpret=interpret,
        **({} if name is None else {"name": name}),
    )(jnp.reshape(prefix_len, (1,)).astype(jnp.int32),
      q.reshape(b * h, t_q, dh), k.reshape(b * h, t_k, dh),
      v.reshape(b * h, t_k, dv))
    return out.reshape(b, h, t_q, dv)


def attention(q, k, v, kv_len=None, causal: bool = True, mesh=None,
              window=None, name=None, prefix=None, prefix_len=None):
    """Dispatching attention: Pallas flash kernel on TPU when the shape
    tiles onto the MXU, XLA einsum otherwise (CPU, tiny prompts). Inference
    only — the kernel defines no VJP; training paths keep the XLA/ring
    implementations (parallel/ring.py).

    ``window`` (static int, optional): query i sees keys (i - window, i]
    (a model's sliding-attention layers); both paths take the same band.

    v may be narrower than q and k (latent attention's prefill: keys of
    192 = 128 + 64 rotary, values of 128): the scale is the keys' and the
    output the values' width. ``name``: the kernel's name in a trace.

    ``prefix`` (static int) with ``prefix_len`` (traced): k and v hold
    ``prefix`` rows before the queries' own, of which every query sees the
    first ``prefix_len`` (EVA attention's summaries of the earlier windows);
    causal, no window, no mesh.

    ``mesh``: the serving mesh when the caller runs under one. Mosaic
    kernels cannot be partitioned by GSPMD, so the kernel call is wrapped
    in a ``shard_map`` with every operand and the result replicated —
    each chip runs the whole single-device kernel, which is the
    replicated-compute contract of ``DecoderLM.set_serving_mesh``.

    Compiled by Mosaic on a v5e (libtpu 0.0.34) at head_dim 128, block 128,
    T in {128, 512, 1024, 1792}, alone and inside prefill, one chip and a
    four-chip mesh (``chip_smoke.py``). Head dims 64 and 256 and the
    256/512 blocks (T >= 4096) lower for the TPU platform but have not been
    compiled on the chip."""
    t_q, t_k = q.shape[2], k.shape[2]
    if prefix is not None:
        if mesh is not None or kv_len is not None or window is not None:
            raise ValueError("a prefix takes no mesh, kv_len or window")
        if (jax.default_backend() == "tpu" and t_q % 128 == 0
                and prefix % 128 == 0 and q.shape[-1] in (64, 128, 256)):
            return flash_attention(q, k, v, causal=True, prefix=int(prefix),
                                   prefix_len=prefix_len, name=name)
        return _prefixed_attention(q, k, v, int(prefix), prefix_len)
    # bigger blocks amortise the online-softmax rescale and MXU ramp-up
    # (block-size choice not measured on the current machine)
    block = 128
    while block < 512 and t_q % (block * 2) == 0 and t_k % (block * 2) == 0 \
            and block * 16 < t_q:
        block *= 2
    use_kernel = (
        kv_len is None
        and jax.default_backend() == "tpu"
        and t_q % block == 0
        and t_k % block == 0
        and q.shape[-1] in (64, 128, 192, 256)
    )
    if not use_kernel:
        if window is None:
            return _xla_attention(q, k, v, causal=causal, kv_len=kv_len)
        return _xla_attention(q, k, v, causal, kv_len, window)
    kernel = functools.partial(
        flash_attention, causal=causal, block_q=block, block_k=block
    )
    if window is not None:
        kernel = functools.partial(kernel, window=int(window))
    if name is not None:
        kernel = functools.partial(kernel, name=name)
    if mesh is not None:
        kernel = jax.shard_map(
            kernel, mesh=mesh, in_specs=(P(), P(), P()), out_specs=P()
        )
    return kernel(q, k, v)
