"""The gated delta rule for serving: a chunked prefill that stops each
sequence's state at its own last token, and a one-token decode step that
reads and writes the state of live lanes only.

A Gated DeltaNet head keeps a matrix ``S`` [Dk, Dv] (key x value) in
float32, zero at a sequence's start. A token with key ``k``, value ``v``,
query ``q`` (``q``, ``k`` L2-normed, ``q`` scaled), log-decay ``g <= 0``
and write strength ``beta`` in (0, 1) does

    S <- exp(g) S;   S <- S + k (beta (v - S^T k))^T;   o = S^T q

``conv_prefill()`` / ``conv_step()``  the causal depthwise convolution in
                      front of it (width ``K``, SiLU, no bias) and the
                      tail of ``K - 1`` inputs a lane keeps between tokens
``gated_delta_prefill()``  whole prompts in chunks of ``CHUNK`` positions
                      (the published kernels' 64): inside a chunk the
                      rule is a unit lower-triangular solve and a few
                      [C, C] and [C, D] matmuls, between chunks a
                      ``lax.scan`` carries ``S``. Positions at or past a
                      sequence's ``lens`` neither decay nor write (``g``
                      and ``beta`` are zeroed there), so the last carry IS
                      the state after the sequence's last real token,
                      whatever bucket it was padded to
``gated_delta_step()``  one token a lane: on a TPU a Pallas kernel whose
                      grid walks the LIVE lanes (scalars prefetched); each
                      program copies a lane's heads in, updates them in
                      VMEM and copies them out, once. The state is aliased
                      in and out: an idle lane's is neither read nor
                      written. Elsewhere the same arithmetic in
                      ``jax.numpy`` under a mask

Operands are the served dtype with float32 accumulation, as the published
kernels take them; the state, its decay and the solve are float32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CHUNK = 64
# rows of the unit-triangular inverse that are substituted one by one;
# larger blocks are put together from two halves
SOLVE_BASE = 16
# value heads one program of the decode kernel holds: blocks of [HEADS,
# Dk, Dv] float32 in and out, two buffers each (2 MB at 8 heads of 128 x
# 128)
STEP_HEADS = 8
L2_EPS = 1e-6


def l2norm(x):
    """x over its last axis, as the published kernels norm q and k."""
    x32 = x.astype(jnp.float32)
    return x32 * lax.rsqrt(jnp.sum(x32 * x32, -1, keepdims=True) + L2_EPS)


# -- the convolution in front -----------------------------------------------------

def conv_prefill(x, w, lens):
    """x [B, T, C] (the layer's q, k, v side by side), w [K, C] -> the
    causal depthwise convolution with SiLU [B, T, C], and each sequence's
    tail [B, K - 1, C]: its inputs at positions ``lens - K + 1 ..
    lens - 1`` (zeros before the sequence's start), which is what the
    next token's convolution reads."""
    t = x.shape[1]
    k = w.shape[0]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    y = sum(padded[:, j:j + t].astype(jnp.float32)
            * w[j].astype(jnp.float32) for j in range(k))
    at = (lens.astype(jnp.int32)[:, None] - (k - 1)
          + jnp.arange(k - 1, dtype=jnp.int32))
    tail = jnp.take_along_axis(x, jnp.maximum(at, 0)[:, :, None], axis=1)
    tail = jnp.where(at[:, :, None] >= 0, tail, jnp.zeros_like(tail))
    return jax.nn.silu(y).astype(x.dtype), tail


def conv_step(x, tail, w, live):
    """x [B, C] this token's input, tail [B, K - 1, C] -> the convolution's
    output at this token [B, C] and the new tail; an idle lane's tail is
    kept as it is."""
    window = jnp.concatenate([tail, x[:, None].astype(tail.dtype)], axis=1)
    y = jnp.sum(window.astype(jnp.float32) * w.astype(jnp.float32)[None], 1)
    new = jnp.where(live[:, None, None], window[:, 1:], tail)
    return jax.nn.silu(y).astype(x.dtype), new


# -- prefill -------------------------------------------------------------------------

def _unit_lower_inverse(a):
    """``(I + a)^-1`` for ``a`` [..., n, n] strictly lower triangular,
    float32: rows by forward substitution up to ``SOLVE_BASE``, above
    that from the two halves' inverses (block substitution). No power of
    ``a`` is formed: keys that repeat make them grow like binomials."""
    n = a.shape[-1]
    if n <= SOLVE_BASE:
        eye = jnp.eye(n, dtype=a.dtype)
        rows = [jnp.broadcast_to(eye[0], a.shape[:-2] + (n,))]
        for i in range(1, n):
            done = jnp.stack(rows, axis=-2)               # [..., i, n]
            rows.append(eye[i] - jnp.einsum(
                "...j,...jn->...n", a[..., i, :i], done,
                precision=lax.Precision.HIGHEST))
        return jnp.stack(rows, axis=-2)
    h = n // 2
    both = _unit_lower_inverse(
        jnp.stack([a[..., :h, :h], a[..., h:, h:]]))
    top, bottom = both[0], both[1]
    cross = -jnp.einsum(
        "...ij,...jk,...kl->...il", bottom, a[..., h:, :h], top,
        precision=lax.Precision.HIGHEST)
    zeros = jnp.zeros_like(cross)
    return jnp.concatenate([
        jnp.concatenate([top, zeros.swapaxes(-1, -2)], axis=-1),
        jnp.concatenate([cross, bottom], axis=-1)], axis=-2)


def _chunk(s, xs):
    """One chunk of every (sequence, head): s [B, H, Dk, Dv] float32 and
    the chunk's q, k [B, H, C, Dk], v [B, H, C, Dv], g, beta [B, H, C]
    -> the new state and the chunk's outputs [B, H, C, Dv]. (Doing what
    stays inside a chunk for all chunks at once, ahead of a scan of three
    matmuls a turn, was 7% SLOWER at 4096 positions on a v5e and held five
    more [T, H, 128] float32 arrays: PERF.md, section 6, PR 38.)"""
    q, k, v, g, beta = xs
    f32 = jnp.float32
    c = q.shape[2]
    hi = lax.Precision.HIGHEST
    cum = jnp.cumsum(g, axis=-1)                                   # [B, H, C]
    lower = jnp.tril(jnp.ones((c, c), bool))
    # exp(cum_i - cum_j) for j <= i; the masked part would overflow
    decay = jnp.where(lower, jnp.exp(
        jnp.where(lower, cum[..., :, None] - cum[..., None, :], 0.0)), 0.0)
    kb = (k.astype(f32) * beta[..., None]).astype(k.dtype)
    vb = (v.astype(f32) * beta[..., None]).astype(v.dtype)
    gram = jnp.einsum("bhid,bhjd->bhij", kb, k, preferred_element_type=f32)
    inv = _unit_lower_inverse(jnp.where(
        jnp.tril(jnp.ones((c, c), bool), -1), gram * decay, 0.0))
    # what each position writes, its own chunk's earlier writes taken off
    u = jnp.einsum("bhij,bhjd->bhid", inv, vb.astype(f32), precision=hi)
    w = jnp.einsum("bhij,bhjd->bhid", inv,
                   kb.astype(f32) * jnp.exp(cum)[..., None], precision=hi)
    new = u - jnp.einsum("bhik,bhkd->bhid", w, s, precision=hi)
    scores = jnp.einsum("bhid,bhjd->bhij", q, k,
                        preferred_element_type=f32) * decay
    o = jnp.einsum("bhik,bhkd->bhid",
                   q.astype(f32) * jnp.exp(cum)[..., None], s, precision=hi)
    o = o + jnp.einsum("bhij,bhjd->bhid", scores, new, precision=hi)
    last = cum[..., -1:]
    s = s * jnp.exp(last)[..., None] + jnp.einsum(
        "bhik,bhid->bhkd", k.astype(f32) * jnp.exp(last - cum)[..., None],
        new, precision=hi)
    return s, o.astype(v.dtype)


@functools.partial(jax.jit, static_argnames=("chunk",))
def gated_delta_prefill(q, k, v, g, beta, lens, chunk: int = CHUNK):
    """Whole prompts. q, k [B, T, H, Dk] (normed, q scaled), v [B, T, H,
    Dv], g, beta [B, T, H] float32, lens [B] int32: a sequence's real
    tokens are its first ``lens``. Returns the outputs [B, T, H, Dv] in
    v's dtype (those at or past ``lens`` are of no use) and each
    sequence's state after its last real token [B, H, Dk, Dv] float32.
    ``chunk``: at most ``SOLVE_BASE``, or that times a power of two."""
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    pad = -t % chunk
    real = jnp.arange(t + pad, dtype=jnp.int32)[None, :] < lens[:, None]

    def chunks(a, mask=False):
        a = jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        if mask:
            a = jnp.where(real[:, :, None], a.astype(jnp.float32), 0.0)
        a = a.reshape(b, (t + pad) // chunk, chunk, *a.shape[2:])
        # [N, B, H, C, ...]: the scan's axis first, heads before positions
        return jnp.moveaxis(jnp.moveaxis(a, 3, 2), 1, 0)

    with jax.named_scope("gated_delta_prefill"):
        s, o = lax.scan(_chunk, jnp.zeros((b, h, dk, dv), jnp.float32), (
            chunks(q), chunks(k), chunks(v), chunks(g, True),
            chunks(beta, True)))
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 2, 3)         # [B, N, C, H, Dv]
    return o.reshape(b, t + pad, h, dv)[:, :t], s


# -- the decode step -------------------------------------------------------------------

def _step_math(s, q, k, v, decay, beta):
    """The rule for one token over any leading axes: s [..., Dk, Dv], q,
    k [..., Dk], v [..., Dv], decay, beta [...]. All float32."""
    s = s * decay[..., None, None]
    seen = jnp.sum(s * k[..., :, None], axis=-2)                   # S^T k
    delta = beta[..., None] * (v - seen)
    s = s + k[..., :, None] * delta[..., None, :]
    return s, jnp.sum(s * q[..., :, None], axis=-2)


def _step_kernel(order_ref, n_ref, s_in, q_ref, k_ref, v_ref, d_ref, b_ref,
                 s_out, o_ref):
    """Grid (B, H / heads): program (i, j) updates heads block j of the
    i-th live lane. Past the live lanes the index maps stay on the last
    block fetched and nothing is computed. Refs: s [1, heads, Dk, Dv]; q,
    k (over Dk), v (over Dv), d, b (one value a head, repeated) [1, heads,
    128]; ``order_ref`` SMEM [B] the live lanes first, ``n_ref`` [1]."""
    i = pl.program_id(0)
    heads, dk, _dv = s_in.shape[1:]
    eye = (lax.broadcasted_iota(jnp.int32, (dk, dk), 0)
           == lax.broadcasted_iota(jnp.int32, (dk, dk), 1))

    def column(row):
        """[1, Dk] along lanes -> [Dk, 1] along sublanes."""
        return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)

    @pl.when(i < n_ref[0])
    def _():
        out = []
        for h in range(heads):
            at = pl.ds(h, 1)
            kc, qc = column(k_ref[0, at, :]), column(q_ref[0, at, :])
            s = s_in[0, h] * d_ref[0, at, :]
            seen = jnp.sum(s * kc, axis=0, keepdims=True)          # [1, Dv]
            delta = b_ref[0, at, :] * (v_ref[0, at, :] - seen)
            s = s + kc * delta
            s_out[0, h] = s
            out.append(jnp.sum(s * qc, axis=0, keepdims=True))
        o_ref[0] = jnp.concatenate(out, axis=0)

    # no live lane at all: the one block every program maps to goes back
    # as it came
    @pl.when(n_ref[0] == 0)
    def _():
        s_out[...] = s_in[...]


@functools.partial(jax.jit, static_argnames=("heads", "interpret"))
def gated_delta_step_kernel(s, q, k, v, decay, beta, live,
                            heads: int = STEP_HEADS, interpret: bool = False):
    """The decode kernel itself: s [B, H, Dk, Dv] float32 (aliased in and
    out), q, k [B, H, Dk], v [B, H, Dv], decay, beta [B, H] float32, live
    [B] bool -> ``(s, o [B, H, Dv] float32)``; an idle lane's ``o`` is
    zeros and its state is not touched. HBM bytes moved: live lanes x H x
    Dk x Dv x 4 B, read once and written once."""
    b, h, dk, dv = s.shape
    heads = min(heads, h)
    if h % heads or dk != dv or dk % 128:
        raise ValueError(f"state {s.shape} does not fit the kernel")
    nh = h // heads
    order = jnp.argsort(~live, stable=True).astype(jnp.int32)
    n = live.sum(dtype=jnp.int32).reshape(1)

    def block(i, j, order, n):
        last = jnp.maximum(n[0] - 1, 0)
        return (order[jnp.minimum(i, last)], jnp.where(i < n[0], j, nh - 1))

    def state(i, j, order, n):
        return (*block(i, j, order, n), 0, 0)

    def rows(i, j, order, n):
        return (*block(i, j, order, n), 0)

    f32 = jnp.float32
    wide = lambda a: jnp.broadcast_to(a.astype(f32)[..., None], (b, h, dk))  # noqa: E731
    s, o = pl.pallas_call(
        _step_kernel,
        out_shape=(jax.ShapeDtypeStruct(s.shape, f32),
                   jax.ShapeDtypeStruct((b, h, dv), f32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, nh),
            in_specs=[pl.BlockSpec((1, heads, dk, dv), state)]
            + [pl.BlockSpec((1, heads, dk), rows)] * 5,
            out_specs=(pl.BlockSpec((1, heads, dk, dv), state),
                       pl.BlockSpec((1, heads, dv), rows)),
        ),
        # with the two scalars counted: the state is operand 2, result 0
        input_output_aliases={2: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        name="gated_delta_step",
        interpret=interpret,
    )(order, n, s, q.astype(f32), k.astype(f32), v.astype(f32),
      wide(decay), wide(beta))
    return s, jnp.where(live[:, None, None], o, 0.0)


def steps_in_kernel(platform, s_shape, mesh=None) -> bool:
    """Whether ``gated_delta_step()``, lowered for ``platform``, is the
    kernel: a TPU, no serving mesh, square heads that fill lanes."""
    return (platform == "tpu" and mesh is None
            and s_shape[2] == s_shape[3] and s_shape[2] % 128 == 0
            and s_shape[1] % min(STEP_HEADS, s_shape[1]) == 0)


@functools.partial(jax.jit, static_argnames=("mesh",))
def gated_delta_step(s, q, k, v, g, beta, live, mesh=None):
    """One token a lane. s [B, H, Dk, Dv] float32; q, k [B, H, Dk] (normed,
    q scaled), v [B, H, Dv]; g, beta [B, H] float32; live [B] bool.
    Returns ``(s, o [B, H, Dv] float32)``: a live lane's state after its
    token and the token's output; an idle lane's state as it was."""
    decay = jnp.exp(g.astype(jnp.float32))
    beta = beta.astype(jnp.float32)

    def kernel(s, q, k, v, decay, beta, live):
        return gated_delta_step_kernel(s, q, k, v, decay, beta, live)

    def masked(s, q, k, v, decay, beta, live):
        f32 = jnp.float32
        new, o = _step_math(s, q.astype(f32), k.astype(f32), v.astype(f32),
                            decay, beta)
        at = live[:, None, None]
        return jnp.where(at[..., None], new, s), jnp.where(at, o, 0.0)

    args = (s, q, k, v, decay, beta, live)
    if not steps_in_kernel("tpu", s.shape, mesh):
        return masked(*args)
    return lax.platform_dependent(*args, tpu=kernel, default=masked)
