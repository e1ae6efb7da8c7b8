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
                      tail of ``K - 1`` inputs a lane keeps between tokens;
                      ``activation=None`` is the lfm2_moe block's gated
                      short convolution, whose gates lie outside; ``bias``
                      is the jamba block's Mamba convolution's
``gated_delta_prefill()``  whole prompts in chunks of ``CHUNK`` positions
                      (the published kernels' 64): inside a chunk the
                      rule is a unit lower-triangular solve and a few
                      [C, C] and [C, D] matmuls, between chunks ``S`` is
                      carried. Positions at or past a sequence's ``lens``
                      neither decay nor write (``g`` and ``beta`` are
                      zeroed there), so the last carry IS the state after
                      the sequence's last real token, whatever bucket it
                      was padded to. On a TPU one Pallas kernel: a block
                      of heads keeps ``S`` in VMEM across a sequence's
                      chunks, a chunk's intermediates never leave VMEM,
                      and the chunks past ``ceil(lens / CHUNK)`` are
                      neither fetched nor computed (``lens`` prefetched;
                      their outputs are zeros). Elsewhere, and as the
                      kernel's oracle, ``_chunk`` under a ``lax.scan``
                      over all of the bucket's chunks
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
# value heads one program of the prefill kernel walks a chunk of: q, k, v
# and the outputs in blocks of [CHUNK, HEADS x D], the state [HEADS, Dk, Dv]
# float32 resident (9 MB of VMEM at 32 heads with their second buffers and
# the scratch). On a v5e a chunk of 32 heads took 48.2 us at 8 heads a
# program, 44.0 at 16 and 41.8 at 32 (PERF.md, section 6, PR 41)
PREFILL_HEADS = 32
L2_EPS = 1e-6


def l2norm(x):
    """x over its last axis, as the published kernels norm q and k."""
    x32 = x.astype(jnp.float32)
    return x32 * lax.rsqrt(jnp.sum(x32 * x32, -1, keepdims=True) + L2_EPS)


# -- the convolution in front -----------------------------------------------------

def _activated(y, activation):
    """The convolution's float32 output under ``activation``: "silu" (the
    Gated DeltaNet's) or None (a gated short convolution's: its gates lie
    outside). A Python constant in every caller's trace."""
    if activation is None:
        return y
    if activation != "silu":
        raise ValueError(f"unknown activation {activation!r}")
    return jax.nn.silu(y)


def conv_prefill(x, w, lens, activation="silu", bias=None):
    """x [B, T, C] (the layer's q, k, v side by side; a gated short
    convolution's ``B * u``; a Mamba mixer's input), w [K, C] (tap j
    multiplies the input ``K - 1 - j`` positions back) -> the causal
    depthwise convolution under ``activation`` [B, T, C], and each
    sequence's tail [B, K - 1, C]: its inputs at positions ``lens - K + 1
    .. lens - 1`` (zeros before the sequence's start), which is what the
    next token's convolution reads. ``bias`` [C] (the jamba block's; a
    Python None in every other caller's trace) goes in before the
    activation."""
    t = x.shape[1]
    k = w.shape[0]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    y = sum(padded[:, j:j + t].astype(jnp.float32)
            * w[j].astype(jnp.float32) for j in range(k))
    at = (lens.astype(jnp.int32)[:, None] - (k - 1)
          + jnp.arange(k - 1, dtype=jnp.int32))
    tail = jnp.take_along_axis(x, jnp.maximum(at, 0)[:, :, None], axis=1)
    tail = jnp.where(at[:, :, None] >= 0, tail, jnp.zeros_like(tail))
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return _activated(y, activation).astype(x.dtype), tail


def conv_step(x, tail, w, live, activation="silu", bias=None):
    """x [B, C] this token's input, tail [B, K - 1, C] -> the convolution's
    output at this token [B, C] and the new tail; an idle lane's tail is
    kept as it is. ``bias``: as ``conv_prefill`` takes it."""
    window = jnp.concatenate([tail, x[:, None].astype(tail.dtype)], axis=1)
    y = jnp.sum(window.astype(jnp.float32) * w.astype(jnp.float32)[None], 1)
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    new = jnp.where(live[:, None, None], window[:, 1:], tail)
    return _activated(y, activation).astype(x.dtype), new


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


def _two_on_the_diagonal(x, same_head):
    """x [C, 2C], two heads' [C, C] side by side -> [2C, 2C] with one on
    each diagonal block and zeros off them: what a product with two heads'
    rows stacked takes, each head's rows meeting its own block alone."""
    return jnp.where(same_head, jnp.concatenate([x, x], axis=0), 0.0)


def _diagonal_blocks_inverses(rows):
    """The base of ``_unit_lower_inverse`` inside the prefill kernel, for
    every ``SOLVE_BASE``-row diagonal block of every head of the program at
    once. ``rows`` [base, L] float32 holds the blocks side by side along
    the lanes: ``rows[i, (block, j)] = a_block[i, j]``, strictly lower
    triangular; the result holds ``(I + a_block)^-1`` the same way. The
    same forward substitution, float32 on the VPU, a column a step: once
    row j is final, every later row i takes ``a[i, j]`` times it off. The
    factor ``a[i, j]`` is wanted under the lanes ``(block, n <= j)`` (a
    finished row is zero right of its diagonal): the column's own lane
    summed over the lanes that follow (rotations by 1, 2, 4, 8; they do
    not wait for the rows, so the 15 steps in turn are a broadcast, a
    multiply and a subtraction each)."""
    base, lanes = rows.shape
    i = lax.broadcasted_iota(jnp.int32, rows.shape, 0)
    j = lax.broadcasted_iota(jnp.int32, rows.shape, 1) % base
    x = (i == j).astype(jnp.float32)
    for at in range(base - 1):
        factor = jnp.where(j == at, rows, 0.0)
        reach = 1
        while reach <= at:
            factor = factor + pltpu.roll(factor, lanes - reach, 1)
            reach *= 2
        x = x - factor * x[at:at + 1]
    return x


def _halves_put_together(x, a, row, col, same_head, base):
    """The merges of ``_unit_lower_inverse`` for two heads: ``x`` [C, 2C],
    the inverses of the ``base``-row diagonal blocks of two heads' ``I +
    a`` side by side (zeros off the blocks) -> [2C, 2C] with each head's
    whole ``(I + a)^-1`` on its diagonal block. Level by level ``x - x
    a_cross x``: what lies between two neighbouring blocks times the
    inverses on either side; only the rows of the lower blocks change."""
    c = a.shape[0]
    hi = lax.Precision.HIGHEST
    f32 = jnp.float32
    x = _two_on_the_diagonal(x, same_head)
    size = base
    while size < c:
        cross = _two_on_the_diagonal(jnp.where(
            (row // (2 * size) == col // (2 * size))
            & (row // size != col // size), a, 0.0), same_head)
        lower = jnp.concatenate(
            [x[at:at + size] for at in range(size, 2 * c, 2 * size)], axis=0)
        moved = jnp.dot(
            jnp.dot(lower, cross, precision=hi, preferred_element_type=f32),
            x, precision=hi, preferred_element_type=f32)            # [C, 2C]
        still = jnp.zeros((size, 2 * c), f32)
        x = x - jnp.concatenate([
            part for at in range(0, c, size)
            for part in (still, moved[at:at + size])], axis=0)
        size *= 2
    return x


def _prefill_kernel(lens_ref, q_ref, k_ref, v_ref, g_ref, b_ref, o_ref, s_ref,
                    cum_ref, beta_ref, a_ref, scores_ref, blocks_ref):
    """Grid (B, H / heads, N), the chunks in turn: program (i, j, n) is
    chunk n of heads block j of sequence i, ``_chunk``'s arithmetic with
    every intermediate in VMEM, two heads at a time: their [C, C] matrices
    lie side by side along the lanes ([C, 2C]: whole registers at C = 64),
    or on the diagonal blocks of one [2C, 2C] where a product takes both.
    Three passes: a loop over the pairs for the chunk's matrix ``a`` and
    the scores of each (kept in ``a_ref``, ``scores_ref`` [heads / 2, C,
    2C]); the substitution of every pair's diagonal blocks in one go
    (``blocks_ref`` [base, heads C]: its steps wait for each other, so
    they are taken once a program, not once a pair); a loop over the pairs
    for the rest. The loops are ``lax.fori_loop``: a pair's code is traced
    and lowered once (unrolled, every prefill executable took 3 s longer to
    lower in each process that starts: PERF.md, section 6, PR 41).
    ``s_ref`` [1, heads, Dk, Dv] float32 is the block's state: its block
    index does not move with n, so it stays in VMEM from the sequence's
    first chunk to its last and goes out once. At or past ``ceil(lens[i] /
    C)`` the index maps park on the last block fetched and the program
    writes zeros to the chunk's outputs, nothing else. Refs: q, k, v [1,
    C, heads D] (head h in lanes ``h D .. (h + 1) D``), g, beta [1, 1, C,
    heads] float32, ``lens_ref`` SMEM [B]; ``cum_ref``, ``beta_ref``
    [heads / 2, C, 2]: a pair's running sums of g and its betas."""
    n = pl.program_id(2)
    c, heads = g_ref.shape[2:]
    d = s_ref.shape[-1]
    base = min(SOLVE_BASE, c)
    length = lens_ref[pl.program_id(0)]
    f32 = jnp.float32
    hi = lax.Precision.HIGHEST
    nt = (((1,), (1,)), ((), ()))
    tn = (((0,), (0,)), ((), ()))

    @pl.when(n == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    @pl.when(n * c >= length)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(n * c < length)
    def _():
        def iota(shape, axis):
            return lax.broadcasted_iota(jnp.int32, shape, axis)

        # two heads' [C, C] side by side: the row, the column in a head's
        # own, and which of the two a lane belongs to
        row, lane = iota((c, 2 * c), 0), iota((c, 2 * c), 1)
        col, first = lane % c, lane < c
        lower = col <= row
        own_block = row // base == col // base
        same_head = iota((2 * c, 2 * c), 0) // c == iota((2 * c, 2 * c), 1) // c
        first_d = iota((c, 2 * d), 1) < d
        # positions at or past the length neither decay nor write
        real = n * c + iota((c, 1), 0) < length
        g = jnp.where(real, g_ref[0, 0], 0.0)                       # [C, heads]
        beta = jnp.where(real, b_ref[0, 0], 0.0)
        # the running sum of g down the chunk, every head of the block at
        # once: exact products with ones, float32 sums
        cums = jnp.dot((iota((c, c), 1) <= iota((c, c), 0)).astype(f32), g,
                       precision=hi, preferred_element_type=f32)
        for pair in range(heads // 2):
            cum_ref[pair] = cums[:, 2 * pair:2 * pair + 2]
            beta_ref[pair] = beta[:, 2 * pair:2 * pair + 2]

        def lanes(pair, width):
            """The lanes of a pair in an array that holds ``width`` a head."""
            return pl.ds(pl.multiple_of(pair * 2 * width, 2 * width), 2 * width)

        def halves(x):
            """[C, 2D], two heads side by side -> [2C, D], one under the
            other."""
            return jnp.concatenate([x[:, :d], x[:, d:]], axis=0)

        def of_each(pair, ref, where):
            """ref[pair] [C, 2], a value a (position, head) -> [C, 2C] or
            [C, 2D], each head's under its own lanes."""
            x = ref[pair]
            return jnp.where(where, x[:, :1], x[:, 1:])

        def weighted(pair, x):
            """x [C, 2D] of a pair times beta, in x's dtype."""
            return (x.astype(f32) * of_each(pair, beta_ref, first_d)).astype(
                x.dtype)

        def matrices(pair, _):
            q, k = q_ref[0, :, lanes(pair, d)], k_ref[0, :, lanes(pair, d)]
            cum = of_each(pair, cum_ref, first)
            along = jnp.sum(jnp.where(row == col, cum, 0.0), axis=0,
                            keepdims=True)                          # [1, 2C]
            decay = jnp.where(lower, jnp.exp(
                jnp.where(lower, cum - along, 0.0)), 0.0)
            # kb k^T and q k^T of both heads in one product: a head's own
            # lie on the diagonal blocks
            dots = lax.dot_general(
                jnp.concatenate([halves(weighted(pair, k)), halves(q)], axis=0),
                halves(k), nt, preferred_element_type=f32)          # [4C, 2C]
            gram = jnp.where(first, dots[:c], dots[c:2 * c])
            a = jnp.where(col < row, gram * decay, 0.0)
            a_ref[pair] = a
            scores_ref[pair] = jnp.where(
                first, dots[2 * c:3 * c], dots[3 * c:]) * decay
            # a block's row i under lane (head, block, j): a[(block, i),
            # (block, j)]
            kept = jnp.where(own_block, a, 0.0)
            blocks_ref[:, lanes(pair, c)] = sum(
                kept[r:r + base] for r in range(0, c, base))

        lax.fori_loop(0, heads // 2, matrices, None)
        blocks_ref[...] = _diagonal_blocks_inverses(blocks_ref[...])

        def rest(pair, _):
            q, k, v = (ref[0, :, lanes(pair, d)] for ref in (q_ref, k_ref, v_ref))
            inv = _halves_put_together(
                jnp.where(own_block, jnp.concatenate(
                    [blocks_ref[:, lanes(pair, c)]] * (c // base), axis=0), 0.0),
                a_ref[pair], row, col, same_head, base)             # [2C, 2C]
            cum = of_each(pair, cum_ref, first_d)
            grown = jnp.exp(cum)
            last = cum[c - 1:]                                      # [1, 2D]
            # what each position writes, its own chunk's earlier writes
            # taken off: [u | w] of one head over the other's
            kbg = weighted(pair, k).astype(f32) * grown
            vb = weighted(pair, v).astype(f32)
            uw = jnp.dot(
                inv, jnp.concatenate([halves(vb), halves(kbg)], axis=1),
                precision=hi, preferred_element_type=f32)           # [2C, 2D]
            qg = q.astype(f32) * grown
            kl = k.astype(f32) * jnp.exp(last - cum)
            new, carried = [], []
            for e in range(2):
                own = slice(e * d, (e + 1) * d)
                rows = slice(e * c, (e + 1) * c)
                s = s_ref[0, 2 * pair + e]
                # [w; q exp(cum)] s in one product
                ws = jnp.dot(
                    jnp.concatenate([uw[rows, d:], qg[:, own]], axis=0), s,
                    precision=hi, preferred_element_type=f32)
                new.append(uw[rows, :d] - ws[:c])
                carried.append(ws[c:])
                s_ref[0, 2 * pair + e] = s * jnp.exp(last[:, own]) + (
                    lax.dot_general(kl[:, own], new[e], tn, precision=hi,
                                    preferred_element_type=f32))
            o = jnp.concatenate(carried, axis=0) + jnp.dot(
                _two_on_the_diagonal(scores_ref[pair], same_head),
                jnp.concatenate(new, axis=0), precision=hi,
                preferred_element_type=f32)                         # [2C, D]
            o_ref[0, :, lanes(pair, d)] = jnp.concatenate(
                [o[:c], o[c:]], axis=1).astype(o_ref.dtype)

        lax.fori_loop(0, heads // 2, rest, None)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def gated_delta_prefill_kernel(q, k, v, g, beta, lens, chunk: int = CHUNK,
                               interpret: bool = False):
    """The prefill kernel itself, ``gated_delta_prefill``'s arguments and
    results. q, k, v go in as they lie, [B, T, H D] (no relayout); g and
    beta as [B, H / heads, T, heads]. Chunks walked: ``ceil(lens / chunk)``
    a sequence; the outputs of the others are zeros."""
    b, t, h, d = q.shape
    heads = min(PREFILL_HEADS, h)
    if not prefills_in_kernel("tpu", q.shape, v.shape, chunk):
        raise ValueError(f"heads {q.shape}, {v.shape} in chunks of {chunk} "
                         "do not fit the kernel")
    pad = -t % chunk
    n, nh = (t + pad) // chunk, h // heads
    lens = lens.astype(jnp.int32)

    def flat(a):
        return jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0))).reshape(
            b, t + pad, h * d)

    def columns(a):
        a = jnp.pad(a.astype(jnp.float32), ((0, 0), (0, pad), (0, 0)))
        return a.reshape(b, t + pad, nh, heads).transpose(0, 2, 1, 3)

    def walked(n_, lens):
        """The chunk program (., ., n_) reads: its own while the sequence
        has it, then the last one it had."""
        return jnp.minimum(n_, jnp.maximum(pl.cdiv(lens, chunk) - 1, 0))

    def wide(i, j, n_, lens):
        return (i, walked(n_, lens[i]), j)

    def narrow(i, j, n_, lens):
        return (i, j, walked(n_, lens[i]), 0)

    o, s = pl.pallas_call(
        _prefill_kernel,
        out_shape=(jax.ShapeDtypeStruct((b, t + pad, h * d), v.dtype),
                   jax.ShapeDtypeStruct((b, h, d, d), jnp.float32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, nh, n),
            in_specs=[pl.BlockSpec((1, chunk, heads * d), wide)] * 3
            + [pl.BlockSpec((1, 1, chunk, heads), narrow)] * 2,
            out_specs=(
                pl.BlockSpec((1, chunk, heads * d),
                             lambda i, j, n_, lens: (i, n_, j)),
                pl.BlockSpec((1, heads, d, d),
                             lambda i, j, n_, lens: (i, j, 0, 0))),
            scratch_shapes=[
                pltpu.VMEM((heads // 2, chunk, 2), jnp.float32)] * 2 + [
                pltpu.VMEM((heads // 2, chunk, 2 * chunk), jnp.float32)] * 2 + [
                pltpu.VMEM((min(SOLVE_BASE, chunk), heads * chunk),
                           jnp.float32)],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="gated_delta_prefill",
        interpret=interpret,
    )(lens, flat(q), flat(k), flat(v), columns(g), columns(beta))
    return o.reshape(b, t + pad, h, d)[:, :t], s


def prefills_in_kernel(platform, q_shape, v_shape, chunk=CHUNK,
                       mesh=None) -> bool:
    """Whether ``gated_delta_prefill()``, lowered for ``platform``, is the
    kernel: ``steps_in_kernel``'s rule (a TPU, no serving mesh, square heads
    that fill lanes), heads in pairs, and chunks two of which fill lanes:
    64 times a power of two, as the halves are put together."""
    h, dk = q_shape[2:]
    return (steps_in_kernel(platform, (q_shape[0], h, dk, v_shape[3]), mesh)
            and h % min(PREFILL_HEADS, h) == 0 and h % 2 == 0
            and chunk % 64 == 0 and chunk & (chunk - 1) == 0)


def _prefill_scanned(q, k, v, g, beta, lens, chunk: int = CHUNK):
    """``gated_delta_prefill`` off a TPU, and the kernel's oracle: ``_chunk``
    under a ``lax.scan`` over all of the bucket's chunks."""
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


@functools.partial(jax.jit, static_argnames=("chunk", "mesh"))
def gated_delta_prefill(q, k, v, g, beta, lens, chunk: int = CHUNK, mesh=None):
    """Whole prompts. q, k [B, T, H, Dk] (normed, q scaled), v [B, T, H,
    Dv], g, beta [B, T, H] float32, lens [B] int32: a sequence's real
    tokens are its first ``lens``. Returns the outputs [B, T, H, Dv] in
    v's dtype (those at or past ``lens`` are of no use: zeros from the
    kernel past a sequence's last chunk) and each sequence's state after
    its last real token [B, H, Dk, Dv] float32. ``chunk``: at most
    ``SOLVE_BASE``, or that times a power of two."""

    def kernel(*args):
        with jax.named_scope("gated_delta_prefill"):
            return gated_delta_prefill_kernel(*args, chunk=chunk)

    def scanned(*args):
        return _prefill_scanned(*args, chunk=chunk)

    args = (q, k, v, g, beta, lens)
    if not prefills_in_kernel("tpu", q.shape, v.shape, chunk, mesh):
        return scanned(*args)
    return lax.platform_dependent(*args, tpu=kernel, default=scanned)


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
