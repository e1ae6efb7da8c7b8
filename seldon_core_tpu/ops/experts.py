"""Drop-free routed experts for serving: the router, the grouped prefill and
a decode step that reads only the experts its live lanes picked.

A layer has ``E`` experts, each a SwiGLU ``(silu(x W1) * (x W3)) W2`` of one
width, held stacked: ``w1``, ``w3`` [E, D, F] and ``w2`` [E, F, D]. Every row
takes its ``k`` best experts by the router's score, with no capacity and no
drop (``parallel/moe.py`` is the training dry-run's top-1 layer with
capacity drops; nothing here shares its code).

``route()``            scores, picks and weights of each row
``held``               a chip that holds ``n`` of a layer's experts from
                       ``lo`` on (its share of an expert-parallel layer)
                       routes over ALL of them and computes its own:
                       ``grouped_experts(held=(lo, n))`` and
                       ``decode_experts(held=(lo, n))`` drop the picks
                       that land elsewhere before anything is grouped or
                       counted as touched, so no expert is read and no
                       row multiplied for them. What the other chips would
                       add is not here; nothing stands in for it.
                       ``grouped_experts(held=(lo, n), n_routed=E)`` does
                       not move them either: after the sort the pairs that
                       landed here come first, and the gather, the three
                       grouped matmuls, the weights and the sum back to
                       rows run over a ROOM of pairs (``room_of()``: what
                       uniform picks send to ``n`` of ``E`` experts and a
                       quarter more, 12,928 of 40,960 where a chip holds
                       a quarter; derived from ``held``, ``E`` and the
                       shapes, no setting). Where more land here than the
                       room holds (a skewed router) the same code runs
                       again over the next room, ``ceil(landed / room)``
                       passes: drop-free whatever the picks. It returns
                       the pairs it moved (``room x passes``) beside the
                       sums: ``Qwen3NextLM.prefill_counted`` adds them up
                       as ``moe_prefill_pairs_moved`` beside
                       ``moe_prefill_pairs_routed``, and the batcher
                       brings both home with a burst
``grouped_experts()``  any number of rows: sort the (row, pick) pairs by
                       expert, three grouped matmuls (``lax.ragged_dot``:
                       on a TPU the compiler's own Mosaic grouped matmul,
                       ``ragged-dot-*`` in a trace), unsort, weighted sum.
                       Compute-bound from a few hundred rows on, where every
                       expert is touched
``decode_experts()``   one row a lane: a Pallas kernel walks the SORTED
                       list of experts that some live lane picked (scalars
                       prefetched to SMEM) and streams each one's ``W1``,
                       ``W3``, ``W2`` from the stacked arrays where they lie
                       in HBM, ONCE, through the pipeline's two buffers. An
                       expert nobody picked is never read, an idle lane
                       routes nowhere. A gather of the touched experts into
                       a temporary would read them twice; a grouped matmul
                       over [E, ...] reads what the row tiles ask for. Off a
                       TPU it is ``grouped_experts()`` with idle lanes'
                       weights at zero

Both return float32 sums over a row's picks; the caller casts.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# rows the grouped path takes in one piece: above it the rows go through
# in groups (``lax.map``), so that the sorted copy of the rows and the three
# products stay a few hundred MB beside a full chip. At 4096 rows x 8 picks
# the experts' matmuls (0.41 TFLOP a layer at Trinity-Mini's widths) take
# about as long as reading the layer's 1.6 GB of experts once more
GROUP_ROWS = 4096
# a share's room of pairs is an ODD number of these rows. The compiler's
# grouped matmul cuts its rows into tiles of the largest of 512, 256, 128
# that divides them, and does a tile's work once for every group that
# touches it: at a share's 80 rows an expert, tiles of 128 do least (128
# experts at the cell's widths, 10,240 rows in groups: 12,928 rows 0.78 ms,
# 12,800 rows 1.07; 640 rows 0.51, 512 rows 0.91: PERF.md section 6, PR 39)
ROOM_TILE = 128
# the rows whose sums one matrix of the way back makes: a block of 128 owns
# at most 128 k products, so its matrix is [128, 128 k]
BAND_ROWS = 128
# the decode kernel's slice of an expert's width: W1 and W3 blocks [D, TF],
# W2 [TF, D], 2 MB each at D = 2048 in bfloat16, two buffers apiece
DECODE_TF = 512
DECODE_VMEM_BYTES = 48 << 20


def route(x, router, bias, k: int, scale: float, score: str = "sigmoid"):
    """x [N, D], router [D, E], bias [E] or None -> picks [N, k] int32 and
    weights [N, k] float32. Scores are ``sigmoid`` of the float32 router
    logits, or with ``score="softmax"`` their softmax over all E; the bias
    enters the SELECTION only; the weights are the picked scores
    normalised to sum to ``scale``."""
    logits = jnp.dot(x, router.astype(x.dtype),
                     preferred_element_type=jnp.float32)
    if score == "softmax":
        s = jax.nn.softmax(logits, axis=-1)
    elif score == "sigmoid":
        s = jax.nn.sigmoid(logits)
    else:
        raise ValueError(f"unknown score function {score!r}")
    _, picks = lax.top_k(s if bias is None else s + bias.astype(jnp.float32), k)
    sel = jnp.take_along_axis(s, picks, axis=-1)
    w = sel / (sel.sum(-1, keepdims=True) + 1e-20) * scale
    return picks.astype(jnp.int32), w


def localise(picks, weights, held, n_experts: int):
    """Picks over a whole layer -> picks into the ``n_experts`` held from
    ``held[0]`` on. A pick that lands elsewhere becomes ``n_experts`` (no
    expert's id: it matches none, sorts last and joins no group) with
    weight 0."""
    lo, n = held
    if n != n_experts:
        raise ValueError(f"held {held} but the stacks hold {n_experts}")
    local = picks - lo
    here = (local >= 0) & (local < n)
    return (jnp.where(here, local, n).astype(picks.dtype),
            jnp.where(here, weights, 0.0))


def _grouped(x, picks, weights, w1, w3, w2, dropped: bool = False):
    n, d = x.shape
    k = picks.shape[1]
    n_experts = w1.shape[0]
    flat = picks.reshape(-1)
    order = jnp.argsort(flat, stable=True)
    xs = x[order // k]                                   # [N k, D] by expert
    sizes = jnp.sum(
        flat[:, None] == jnp.arange(n_experts, dtype=flat.dtype)[None, :],
        axis=0, dtype=jnp.int32)
    a = lax.ragged_dot(xs, w1, sizes, preferred_element_type=jnp.float32)
    g = lax.ragged_dot(xs, w3, sizes, preferred_element_type=jnp.float32)
    h = (jax.nn.silu(a) * g).astype(x.dtype)
    y = lax.ragged_dot(h, w2, sizes, preferred_element_type=jnp.float32)
    by_expert = weights.reshape(-1)[order][:, None]
    if dropped:
        # rows past the last group belong to no expert and were given to
        # none: whatever lies there is not a product
        y = jnp.where(flat[order][:, None] < n_experts, y, 0.0)
    y = (y * by_expert).astype(x.dtype)
    back = jnp.argsort(order)                             # the unsort
    return y[back].reshape(n, k, d).astype(jnp.float32).sum(axis=1)


def room_of(pairs: int, held, n_routed: int) -> int:
    """The (row, pick) pairs a share's grouped path moves in one pass: what
    ``pairs`` uniform picks over ``n_routed`` experts send to the ``held[1]``
    held ones and a quarter more (5/16 of all pairs where a chip holds a
    quarter of the layer), rounded up to an odd number of ``ROOM_TILE``;
    never more than there are."""
    expected = -(-pairs * held[1] * 5 // (n_routed * 4))
    return min(pairs, (-(-expected // ROOM_TILE) | 1) * ROOM_TILE)


def _grouped_held(x, picks, weights, w1, w3, w2, room: int):
    """``_grouped(dropped=True)`` over ``room`` pairs at a time. picks [N,
    k] are local already (``localise()``): after the sort the ``c`` pairs
    that landed here are the first ``c`` of the order, and pass ``p`` of
    ``ceil(c / room)`` takes those from ``p room`` on: gathers their rows,
    multiplies them (each pass's groups are the experts' groups cut to its
    window), weights them, and adds them to their rows' sums. A pair that
    landed elsewhere is never gathered, and every pair that landed here
    is in some pass. Returns ``(float32 [N, D], pairs moved)``.

    The way back to row order: a pass's pairs sorted by their place in
    ``picks`` lie row by row, a row's beside each other, so rows ``b T ..
    (b + 1) T - 1`` own a stretch of at most ``T k`` of them, and a [T, T
    k] matrix of ones and zeros times that stretch is each row's float32
    sum of its own bfloat16 products: nothing of [N, k, D] is built."""
    n, d = x.shape
    k = picks.shape[1]
    n_experts = w1.shape[0]
    pairs = n * k
    flat = picks.reshape(-1)
    order = jnp.argsort(flat, stable=True)
    sizes = jnp.sum(
        flat[:, None] == jnp.arange(n_experts, dtype=flat.dtype)[None, :],
        axis=0, dtype=jnp.int32)
    ends = jnp.cumsum(sizes)
    landed = ends[-1]
    passes = -(-landed // room)
    # every pass's window lies inside the order
    order = jnp.pad(order, (0, -pairs % room)).astype(jnp.int32)
    by_pair = weights.reshape(-1)
    band = math.gcd(n, BAND_ROWS)
    stretch = min(band * k, room)
    firsts = jnp.arange(0, n, band, dtype=jnp.int32)

    def one_pass(p, out):
        lo = p * room
        real = lo + jnp.arange(room, dtype=jnp.int32) < landed
        pair = lax.dynamic_slice(order, (lo,), (room,))
        xs = x[pair // k]                                 # [room, D] by expert
        here = (jnp.clip(ends, lo, lo + room)
                - jnp.clip(ends - sizes, lo, lo + room))
        a = lax.ragged_dot(xs, w1, here, preferred_element_type=jnp.float32)
        g = lax.ragged_dot(xs, w3, here, preferred_element_type=jnp.float32)
        h = (jax.nn.silu(a) * g).astype(x.dtype)
        y = lax.ragged_dot(h, w2, here, preferred_element_type=jnp.float32)
        # rows past the last group were given to no expert: not a product
        y = (jnp.where(real[:, None], y, 0.0)
             * by_pair[pair][:, None]).astype(x.dtype)
        back = jnp.argsort(jnp.where(real, pair, pairs), stable=True)
        y = y[back]                                       # [room, D] by row
        row = jnp.where(real, pair // k, n)[back]         # ascending
        start = jnp.minimum(
            jnp.sum(row[None, :] < firsts[:, None], axis=1, dtype=jnp.int32),
            room - stretch)

        def rows_sum(first, start):
            own = lax.dynamic_slice(row, (start,), (stretch,))[None, :] == (
                first + jnp.arange(band, dtype=jnp.int32))[:, None]
            return jnp.dot(
                own.astype(x.dtype),
                lax.dynamic_slice(y, (start, 0), (stretch, d)),
                precision=lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32)

        return out + lax.map(
            lambda r: rows_sum(*r), (firsts, start)).reshape(n, d)

    with jax.named_scope("held_experts_prefill"):
        out = lax.fori_loop(0, passes, one_pass, jnp.zeros((n, d), jnp.float32))
    return out, passes * room


@functools.partial(jax.jit,
                   static_argnames=("group_rows", "held", "n_routed"))
def grouped_experts(x, picks, weights, w1, w3, w2, group_rows: int = GROUP_ROWS,
                    held=None, n_routed=None):
    """x [N, D] rows, picks / weights [N, k] (``route()``), the stacked
    experts -> float32 [N, D]: sum over a row's picks of weight x expert(x).
    Drop-free whatever the picks. More than ``group_rows`` rows go through
    in equal groups of at most that many. ``held=(lo, n)``: the stacks are
    experts ``lo .. lo + n - 1`` of the ``n_routed`` the picks range over,
    a pick outside them adds nothing (``localise()``) and is not moved
    either: a group's pairs go through ``room_of()`` at a time, and the
    result is ``(float32 [N, D], pairs moved)``, the second ``room x
    passes`` summed over the groups."""
    n = x.shape[0]
    groups = -(-n // group_rows)
    while n % groups:
        groups += 1
    if held is None:
        def one(x, picks, weights):
            return _grouped(x, picks, weights, w1, w3, w2)
    else:
        if not n_routed:
            raise ValueError(f"held {held} of how many experts: n_routed")
        picks, weights = localise(picks, weights, held, w1.shape[0])
        room = room_of(n // groups * picks.shape[1], held, n_routed)

        def one(x, picks, weights):
            return _grouped_held(x, picks, weights, w1, w3, w2, room)
    if groups == 1:
        return one(x, picks, weights)
    split = lambda a: a.reshape(groups, n // groups, *a.shape[1:])  # noqa: E731
    out = lax.map(lambda r: one(*r), (split(x), split(picks), split(weights)))
    if held is None:
        return out.reshape(n, x.shape[1])
    return out[0].reshape(n, x.shape[1]), out[1].sum()


def touched_experts(picks, live, n_experts: int):
    """The experts some LIVE lane picked: ``(ids [E] int32, n)`` with the
    ``n`` touched ids first, ascending (the rest of ``ids`` is 0 and is
    never read as an expert). picks [B, k]; live [B] bool."""
    e = jnp.arange(n_experts, dtype=jnp.int32)
    hit = (picks[:, :, None] == e[None, None, :]) & live[:, None, None]
    touched = hit.any(axis=(0, 1))                        # [E]
    place = jnp.cumsum(touched.astype(jnp.int32)) - 1     # its slot in ids
    ids = jnp.sum(
        jnp.where(touched[:, None] & (place[:, None] == e[None, :]),
                  e[:, None], 0), axis=0)
    return ids.astype(jnp.int32), touched.sum(dtype=jnp.int32)


def _decode_kernel(ids_ref, n_ref, x_ref, picks_ref, wts_ref, w1_ref, w3_ref,
                   w2_ref, o_ref):
    """Grid (E, F / TF): step (i, f) adds slice f of the i-th touched
    expert. Past the touched ones the index maps stay on the last block
    fetched, so nothing more is copied, and nothing is computed."""
    i, f = pl.program_id(0), pl.program_id(1)

    @pl.when((i == 0) & (f == 0))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(i < n_ref[0])
    def _():
        x = x_ref[...]
        a = jnp.dot(x, w1_ref[0], preferred_element_type=jnp.float32)
        g = jnp.dot(x, w3_ref[0], preferred_element_type=jnp.float32)
        # each lane's weight for this expert: 0 where it did not pick it
        c = jnp.sum(jnp.where(picks_ref[...] == ids_ref[i], wts_ref[...], 0.0),
                    axis=-1, keepdims=True)               # [B, 1]
        h = (jax.nn.silu(a) * g * c).astype(x.dtype)
        o_ref[...] += jnp.dot(h, w2_ref[0], preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("tf", "interpret"))
def touched_experts_ffn(x, picks, weights, ids, n, w1, w3, w2,
                        tf: int = DECODE_TF, interpret: bool = False):
    """The decode kernel itself. x [B, D]; picks [B, k] int32; weights [B, k]
    float32 (an idle lane's: zeros); ``ids`` [E], ``n`` [1] from
    ``touched_experts()``; the stacked experts, left in HBM. Float32 [B, D].
    HBM bytes read: ``n`` x the three matrices of one expert."""
    b, d = x.shape
    n_experts, _, width = w1.shape
    tf = min(tf, width)
    # the widest slice of whole registers that divides the width: 512 at
    # 1024 and 512, 384 at 768
    while width % tf and tf > 128:
        tf -= 128
    if width % tf:
        raise ValueError(f"expert width {width} is no multiple of {tf}")
    nf = width // tf

    def expert(i, f, ids, n):
        last = jnp.maximum(n[0] - 1, 0)
        return ids[jnp.minimum(i, last)], jnp.where(i < n[0], f, nf - 1)

    def cols(i, f, ids, n):
        e, f = expert(i, f, ids, n)
        return e, 0, f

    def rows(i, f, ids, n):
        e, f = expert(i, f, ids, n)
        return e, f, 0

    whole = lambda i, f, ids, n: (0, 0)  # noqa: E731
    return pl.pallas_call(
        _decode_kernel,
        out_shape=jax.ShapeDtypeStruct((b, d), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n_experts, nf),
            in_specs=[
                pl.BlockSpec((b, d), whole),
                pl.BlockSpec(picks.shape, whole),
                pl.BlockSpec(weights.shape, whole),
                pl.BlockSpec((1, d, tf), cols),
                pl.BlockSpec((1, d, tf), cols),
                pl.BlockSpec((1, tf, d), rows),
            ],
            out_specs=pl.BlockSpec((b, d), whole),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=DECODE_VMEM_BYTES,
        ),
        name="touched_experts_ffn",
        interpret=interpret,
    )(ids, n.reshape(1), x, picks, weights, w1, w3, w2)


def decodes_touched(platform, x_shape, w1_shape, mesh=None) -> bool:
    """Whether ``decode_experts()``, lowered for ``platform``, is the
    kernel: a TPU, no serving mesh (Mosaic kernels are not partitioned),
    widths that fill lanes, rows that fill a bfloat16 tile."""
    return (
        platform == "tpu" and mesh is None
        and x_shape[0] % 16 == 0
        and x_shape[1] % 128 == 0 and w1_shape[2] % 128 == 0
    )


@functools.partial(jax.jit, static_argnames=("mesh", "held"))
def decode_experts(x, picks, weights, live, w1, w3, w2, mesh=None, held=None):
    """One decode step's routed experts. x [B, D]; picks, weights [B, k];
    live [B] bool. Returns ``(float32 [B, D], experts touched, rows
    routed)``: an idle lane adds nothing to either count, reads no expert
    and gets zeros. ``held=(lo, n)`` as ``grouped_experts()`` takes it: a
    pick that lands elsewhere touches nothing here; rows routed still
    counts every pick of a live lane."""
    weights = jnp.where(live[:, None], weights, 0.0)
    if held is not None:
        picks, weights = localise(picks, weights, held, w1.shape[0])
    ids, n = touched_experts(picks, live, w1.shape[0])
    routed = live.sum(dtype=jnp.int32) * picks.shape[1]

    def kernel(x, picks, weights, ids, n, w1, w3, w2):
        return touched_experts_ffn(x, picks, weights, ids, n, w1, w3, w2)

    def grouped(x, picks, weights, ids, n, w1, w3, w2):
        if held is None:
            return grouped_experts(x, picks, weights, w1, w3, w2)
        # the picks are local already
        return _grouped(x, picks, weights, w1, w3, w2, True)

    args = (x, picks, weights, ids, n, w1, w3, w2)
    if not decodes_touched("tpu", x.shape, w1.shape, mesh):
        return grouped(*args), n, routed
    return lax.platform_dependent(*args, tpu=kernel, default=grouped), n, routed
