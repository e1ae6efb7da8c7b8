"""Blocked (flash) attention as a Pallas TPU kernel.

Motivation: prefill attention materialises the full [T, T] score matrix
in XLA — at long prompts that is O(T^2) HBM traffic and VMEM spill. The
flash kernel streams K/V blocks through VMEM with an online-softmax
accumulator, so scores never leave VMEM and HBM traffic is O(T * Dh).
No reference counterpart (the reference ships no kernels at all); the
algorithm is the standard FlashAttention blocking, tiled for the MXU
(256 x 512 tiles, f32 accumulators, bf16 operands).

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
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

NEG_INF = -1e30
# Whole keys and values of one head are resident, two buffers each, rows
# padded to 128 lanes: up to this many bytes of them the compiler's default
# scoped limit (16 MiB) holds the call (14,336 keys at a head of 64, on the
# chip: PR 51); past it (9,728 keys of 192 beside values of 128: "exceeded
# scoped vmem limit by 416.0K" with a sink's epilogue) the call asks for
# what it holds and ``RESIDENT_ROOM`` more
RESIDENT_DEFAULT = 14 << 20
RESIDENT_ROOM = 16 << 20


def _xla_attention(q, k, v, causal: bool, kv_len=None, window=None,
                   block=None, sink=None):
    """Reference attention, same contract as the kernel — delegates to
    parallel/ring.full_attention so the fallback and the trained/ring
    paths share ONE copy of the math. ``window``: the kernel's band, as
    the same masked dots; ``block``: its mask that is open inside a block."""
    from ..parallel.ring import full_attention

    if sink is not None:
        if not causal or kv_len is not None or block is not None:
            raise ValueError("a sink is causal, takes no kv_len and no block")
        return _banded_attention(q, k, v, window, sink)
    if window is None and block is None:
        return full_attention(q, k, v, causal=causal, kv_len=kv_len)
    if not causal or kv_len is not None or not (window is None or block is None):
        raise ValueError("a window or a block is causal, takes no kv_len, "
                         "and not the other")
    if block is not None:
        return _block_attention(q, k, v, block)
    return _banded_attention(q, k, v, window)


def _block_attention(q, k, v, block: int):
    """Attention causal over blocks of ``block`` positions (a power of two)
    and open inside one: query i sees key j iff j <= i | (block - 1), the
    last position of i's block. full_attention's math under that mask."""
    scale = 1.0 / (q.shape[-1] ** 0.5)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    row = jnp.arange(q.shape[2])[:, None]
    col = jnp.arange(k.shape[2])[None, :]
    s = jnp.where(((row | (block - 1)) >= col)[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)).astype(q.dtype)


def _banded_attention(q, k, v, window, sink=None):
    """Causal attention in which query i sees key j iff i - window < j <= i
    (``window`` None: every j <= i): full_attention's math under the band's
    mask. ``sink`` [H]: a logit a head that joins the softmax as one more
    column, with no value row."""
    scale = 1.0 / (q.shape[-1] ** 0.5)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    row = jnp.arange(q.shape[2])[:, None]
    col = jnp.arange(k.shape[2])[None, :]
    seen = row >= col
    if window is not None:
        seen = seen & (col > row - window)
    s = jnp.where(seen[None, None], s, NEG_INF)
    if sink is None:
        p = jax.nn.softmax(s, axis=-1)
    else:
        column = jnp.broadcast_to(
            sink.astype(jnp.float32)[None, :, None, None], (*s.shape[:3], 1))
        p = jax.nn.softmax(jnp.concatenate([s, column], -1), -1)[..., :-1]
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


def _flash_sink_kernel(len_ref, sink_ref, *refs, heads, **how):
    """``_flash_kernel`` for a layer whose softmax has a sink: ``sink_ref``
    (SMEM [H] float32, scalar-prefetched) holds a logit a query head, and
    program ``bh`` is head ``bh mod H``'s."""
    _flash_kernel(len_ref, *refs, sink=sink_ref[pl.program_id(0) % heads],
                  **how)


def _flash_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, *, block_q, block_k,
                  causal, window=None, prefix=None, block=None, sink=None):
    """One (bh, q-block) program: stream K/V tiles with online softmax.

    q_ref: [1, block_q, Dh]; k_ref: [1, Tk, Dh]; v_ref: [1, Tk, Dv] and
    o_ref: [1, block_q, Dv] (whole keys for this bh resident in VMEM —
    serving-sized Tk*Dh fits easily). Dv is Dh but where a family's keys
    are wider than its values (latent attention: 192 and 128).

    The tile is [block_q, block_k] and the two may differ: an iteration's
    time is the chain through the matrix unit and back, not its FLOPs, so
    a key tile of 512 columns (four of the unit's weight tiles side by
    side) under 256 query rows costs 0.8 us where 128 x 128 costs 0.4 for
    an eighth of the work (PERF.md section 6, PR 46). The operands go
    into the two products in the arrays' own dtype (bfloat16 when
    serving): Mosaic's default precision rounds a 32-bit operand to
    bfloat16, so a float32 cast buys no bit; scores, running max, running
    sum and the accumulator are float32.

    Where ``block_k`` does not divide the keys, what is left over is the
    walk's FIRST tile, ``lead`` columns wide (every causal row sees column
    0, so every q-block needs it), and the whole tiles follow from there.
    ``window`` (static, with ``causal``, no lead): row i sees columns
    (i - window, i]; the walk starts at the tile that holds the q-block's
    first row's first column and key tiles wholly left of the band are
    never read. ``prefix`` (static, with ``causal``) and ``len_ref`` (SMEM
    [1], scalar-prefetched): the first ``prefix`` keys are a prefix of
    which every row sees those below ``len_ref[0]``; the walk takes the
    prefix's ``ceil(len / block_k)`` tiles and then the causal ones, and a
    prefix tile past the visible ones is never read. ``block`` (static, a
    power of two that divides 128, with ``causal``, no window): the mask is
    causal over blocks of that many positions and open inside one, row i
    sees columns up to i | (block - 1); only the compare in the diagonal
    tiles differs, a q-block's last row ends a block. ``sink`` (a float32
    scalar): a logit that joins every row's softmax after its last tile
    and has no value row.
    """
    qb = pl.program_id(1)
    scale = 1.0 / np.sqrt(q_ref.shape[-1])
    # scaled once a q-block, in float32, then rounded to the operands'
    # dtype (scaling the float32 scores instead costs 3-5% a call)
    q = (q_ref[0].astype(jnp.float32) * scale).astype(q_ref.dtype)
    off = prefix or 0
    t_own = k_ref.shape[1] - off
    lead = t_own % block_k
    row0 = qb * block_q
    row = row0 + lax.broadcasted_iota(jnp.int32, (block_q, 1), 0)

    def own(col):
        seen = row >= col if block is None else (row | (block - 1)) >= col
        if window is not None:
            seen = seen & (col > row - window)
        return seen

    def tile(i, carry, base, col0=0, width=block_k, seen_of=None,
             hides_rows=False):
        """Keys ``[base + i * block_k, + width)``, whose first column is
        ``col0 + i * block_k`` to ``seen_of``."""
        o, m, l = carry
        # the traced start is tile-aligned: say so, or Mosaic cannot
        # prove the sublane slice lands on a tile edge
        start = pl.multiple_of(base + i * block_k, 128)
        kb = k_ref[0, pl.ds(start, width), :]
        vb = v_ref[0, pl.ds(start, width), :]
        s = lax.dot_general(
            q, kb, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [block_q, width]
        if seen_of is not None:
            col = col0 + i * block_k + lax.broadcasted_iota(
                jnp.int32, (1, width), 1)
            seen = seen_of(col)
            s = jnp.where(seen, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        # Masked entries hold NEG_INF (finite -1e30): a causal row admits
        # column 0 in the walk's first tile, so m_new is finite from there
        # on and exp(NEG_INF - m_new) underflows to exactly 0 — no NaN, no
        # select needed in the hot loop. A tile that hides ALL its columns
        # from a row that has seen none yet would leave m_new == NEG_INF and
        # p == 1 per entry (an unweighted mean of V, not zeros): the band's
        # first tile does that to the q-block's later rows, and a prefix
        # tile's invisible tail comes before any row has a finite m.
        p = jnp.exp(s - m_new)
        if hides_rows:
            p = jnp.where(seen, p, 0.0)
        l = l * alpha + p.sum(axis=-1, keepdims=True)
        o = o * alpha + lax.dot_general(
            p.astype(vb.dtype), vb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return o, m_new, l

    carry = (jnp.zeros((block_q, v_ref.shape[-1]), jnp.float32),
             jnp.full((block_q, 1), NEG_INF, jnp.float32),
             jnp.zeros((block_q, 1), jnp.float32))
    if prefix is not None:
        visible = len_ref[0]
        carry = lax.fori_loop(
            0, (visible + block_k - 1) // block_k,
            functools.partial(tile, base=0, seen_of=lambda col: col < visible,
                              hides_rows=True), carry)
    seen_of = own if causal else None
    if lead:
        carry = tile(0, carry, off, width=lead, seen_of=seen_of)
    first, n_k = 0, (t_own - lead) // block_k
    if causal:
        # tiles fully above the diagonal contribute nothing: stop at the
        # one that holds the q-block's last row
        n_k = jnp.minimum(
            n_k, (row0 + block_q - lead + block_k - 1) // block_k)
    if window is not None:
        first = jnp.maximum(0, (row0 - window + 1) // block_k)
    o, m, l = lax.fori_loop(
        first, n_k,
        functools.partial(tile, base=off + lead, col0=lead, seen_of=seen_of,
                          hides_rows=window is not None), carry)
    if sink is not None:
        m_all = jnp.maximum(m, sink)
        alpha = jnp.exp(m - m_all)
        o, l = o * alpha, l * alpha + jnp.exp(sink - m_all)
    # l == 0 is unreachable via the causal dispatch (see the note in the
    # tile); kept as a belt against 0/0 if the kernel is rebuilt with a
    # row-hiding mask
    l = jnp.where(l == 0.0, 1.0, l)
    o_ref[0] = (o / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "block_q", "block_k", "interpret", "window",
                     "name", "prefix", "block"),
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
    block=None,
    sink=None,
):
    """Pallas blocked attention. q [B,H,Tq,Dh], k [B,H,Tk,Dh], v
    [B,H,Tk,Dv] (Dv = Dh everywhere but latent attention's prefill).
    Tq must divide by block_q, and Tk and both blocks by 128 (use
    :func:`attention` for the dispatching fallback and the tile the chip
    was measured to want); ``block_k`` need not divide Tk but under a
    window. ``window`` (static int, causal only): query i sees keys
    (i - window, i]. ``name``: the kernel's name in a trace, where a
    caller wants its own. ``prefix`` (static int, causal only, a multiple
    of 128) with ``prefix_len`` (a traced int32 scalar): k and v are [B, H,
    prefix + Tq, .], their first ``prefix`` rows a prefix every query sees
    the first ``prefix_len`` rows of, the others the queries' own
    positions. ``block`` (static int, causal only, no window): the mask is
    causal over blocks of ``block`` positions and open inside one.
    ``sink`` ([H] float32, optional): a learned logit a query head that
    joins every row's softmax and has no value row."""
    if window is not None and not causal:
        raise ValueError("a window is causal")
    if block is not None and (not causal or window is not None
                              or block & (block - 1) or 128 % block):
        raise ValueError(f"a block of {block}: causal, no window, a power "
                         "of two that divides 128")
    b, h, t_q, dh = q.shape
    t_k, dv = k.shape[2], v.shape[-1]
    if prefix is not None and (
            not causal or window is not None or prefix % 128
            or t_k != prefix + t_q or block_k > t_q):
        raise ValueError(
            f"a prefix of {prefix} before {t_q} causal keys: Tk={t_k}, "
            f"block_k {block_k}, no window")
    if t_q % block_q or t_k % 128 or block_q % 128 or block_k % 128 \
            or (window is not None and t_k % block_k):
        raise ValueError(
            f"Tq={t_q} / Tk={t_k} must tile by block ({block_q}, {block_k})"
        )
    kernel = functools.partial(
        _flash_kernel, block_q=block_q, block_k=block_k, causal=causal,
        window=None if window is None else int(window),
        prefix=None if prefix is None else int(prefix),
        **({} if block is None else {"block": int(block)}))
    visible = jnp.zeros((1,), jnp.int32) if prefix is None else \
        jnp.reshape(prefix_len, (1,)).astype(jnp.int32)
    lanes = lambda d: -(-d // 128) * 128  # noqa: E731
    resident = 2 * t_k * (lanes(dh) + lanes(dv)) * q.dtype.itemsize
    limits = {} if resident <= RESIDENT_DEFAULT else {
        "compiler_params": pltpu.CompilerParams(
            vmem_limit_bytes=resident + RESIDENT_ROOM)}
    scalars = (visible,)
    if sink is not None:
        kernel = functools.partial(_flash_sink_kernel, heads=h, **kernel.keywords)
        scalars += (sink.astype(jnp.float32).reshape(h),)
    out = pl.pallas_call(
        kernel,
        # under shard_map the result varies over the mesh axes q does
        out_shape=jax.ShapeDtypeStruct(
            (b * h, t_q, dv), q.dtype, vma=jax.typeof(q).vma
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(b * h, t_q // block_q),
            in_specs=[
                pl.BlockSpec((1, block_q, dh), lambda bh, i, *_: (bh, i, 0)),
                pl.BlockSpec((1, t_k, dh), lambda bh, i, *_: (bh, 0, 0)),
                pl.BlockSpec((1, t_k, dv), lambda bh, i, *_: (bh, 0, 0)),
            ],
            out_specs=pl.BlockSpec(
                (1, block_q, dv), lambda bh, i, *_: (bh, i, 0)),
        ),
        interpret=interpret,
        **({} if name is None else {"name": name}),
        **limits,
    )(*scalars, q.reshape(b * h, t_q, dh), k.reshape(b * h, t_k, dh),
      v.reshape(b * h, t_k, dv))
    return out.reshape(b, h, t_q, dv)


def _tile(t_q, t_k, window=None, prefix=0):
    """The (block_q, block_k) the kernel walks for a call's shapes.

    Measured on a v5e (PERF.md section 6, PR 46; the kernel alone, 32 heads
    of 128 but where said, us a call at the old rule's 128 x 128 -> at this
    tile): T 512 157 -> 70, 1024 500 -> 165, 1792 1400 -> 424 (a lead tile
    of 256), 2048 1791 -> 481, 4096 2562 (256 x 256) -> 1500, 4096 under a
    window of 2048 2089 -> 1446; 16 heads of 256 at 4096 1483 -> 1300; keys
    of 192 and values of 128 at 1792 1537 -> 562; 2048 behind 384 visible
    rows of a prefix of 896 2336 -> 669. A key tile wider than 512 is
    slower (1024: +3 to +17%), 512 query rows read within 1% of 256 up to
    2048 and 2% under them from 4096, and an interior tile that skips the
    mask, or two tiles a loop body, gain nothing at this tile."""
    block_k = 512
    own = t_k - prefix
    if window is not None and own % block_k:
        # the band's walk has no lead tile: the largest that divides
        block_k = 256 if own % 256 == 0 else 128
    return (256 if t_q % 256 == 0 else 128), min(block_k, own)


def attention(q, k, v, kv_len=None, causal: bool = True, mesh=None,
              window=None, name=None, prefix=None, prefix_len=None,
              block=None, sink=None):
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

    ``block`` (static int, optional: a power of two): causal over blocks of
    that many positions and open inside one (generation by blocks); both
    paths take the same mask. A Python ``None`` for every other caller,
    whose programs it leaves as they were.

    ``sink`` ([H] float32, optional): a learned logit a query head that
    joins every row's softmax and has no value row (causal, with or
    without a window, no mesh); a Python ``None`` likewise.

    ``mesh``: the serving mesh when the caller runs under one. Mosaic
    kernels cannot be partitioned by GSPMD, so the kernel call is wrapped
    in a ``shard_map`` with every operand and the result replicated —
    each chip runs the whole single-device kernel, which is the
    replicated-compute contract of ``DecoderLM.set_serving_mesh``.

    The tile is ``_tile``'s, from the call's shapes alone. Compiled by
    Mosaic and run on a v5e (libtpu 0.0.34) at that tile for 32 heads of
    128 at T in {128, 512, 1024, 1792, 2048, 4096}, 4096 under a window of
    2048, 2048 behind a prefix of 896; 16 heads of 128 at 512 and 1024, of
    256 at 512 and 4096; keys of 192 with values of 128 at 256, 512, 1792
    and 6144; alone (PERF.md section 6, PR 46: the table) and inside the
    prefills of the benchmark's cells. Not run on the chip at this tile:
    the call under a mesh (``chip_smoke.py``'s four-chip leg; it lowers
    for the platform in ``tests/test_flash_attention.py``) and head dim 64
    (it compiles for a described v5e in ``tests/test_burst_hlo.py``)."""
    t_q, t_k = q.shape[2], k.shape[2]
    if prefix is not None and (
            mesh is not None or kv_len is not None or window is not None):
        raise ValueError("a prefix takes no mesh, kv_len or window")
    if sink is not None and (mesh is not None or prefix is not None):
        raise ValueError("a sink takes no mesh and no prefix")
    use_kernel = (
        kv_len is None
        and jax.default_backend() == "tpu"
        and t_q % 128 == 0
        and t_k % 128 == 0
        and (prefix or 0) % 128 == 0
        and q.shape[-1] in (64, 128, 192, 256)
    )
    if not use_kernel:
        if prefix is not None:
            return _prefixed_attention(q, k, v, int(prefix), prefix_len)
        return _xla_attention(q, k, v, causal, kv_len, window, block, sink)
    block_q, block_k = _tile(t_q, t_k, window, prefix or 0)
    kernel = functools.partial(
        flash_attention, causal=causal, block_q=block_q, block_k=block_k,
        window=None if window is None else int(window), name=name,
        prefix=None if prefix is None else int(prefix), prefix_len=prefix_len,
        **({} if block is None else {"block": int(block)}),
        **({} if sink is None else {"sink": sink}),
    )
    if mesh is not None:
        kernel = jax.shard_map(
            kernel, mesh=mesh, in_specs=(P(), P(), P()), out_specs=P()
        )
    return kernel(q, k, v)
