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
                       landed here come first, and the gather, the
                       experts' SwiGLU, the weights and the sum back to
                       rows run over a ROOM of pairs (``room_of()``: what
                       uniform picks send to ``n`` of ``E`` experts and a
                       quarter more, 12,928 of 40,960 where a chip holds
                       a quarter; derived from ``held``, ``E`` and the
                       shapes, no setting). Where more land here than the
                       room holds (a skewed router) the same code runs
                       again over the next room, ``ceil(landed / room)``
                       passes: drop-free whatever the picks. It counts
                       the pairs it moved (``room x passes``) and the tile
                       rows its kernel worked: a family's
                       ``prefill_counted`` adds them up as
                       ``moe_prefill_pairs_moved`` and
                       ``moe_prefill_tile_rows`` beside
                       ``moe_prefill_pairs_routed``, and the batcher
                       brings them home with a burst
``grouped_experts()``  any number of rows: sort the (row, pick) pairs by
                       expert, the experts' SwiGLU over the sorted pairs
                       (``_pairs_ffn()``), unsort, weighted sum; returns the
                       sums and ``GROUPED_COUNTS``. On a TPU the SwiGLU is
                       ``grouped_swiglu()``, a Pallas kernel of this file
                       (``grouped_swiglu`` in a trace): the sorted pairs
                       are cut into row tiles, and a walk prefetched to
                       SMEM (``tile_visits()``) works a tile once for every
                       group that has a row in it: the group's ``W1`` and
                       ``W3`` side by side, then its ``W2``, copied from the
                       stacked arrays where they lie, once, a group ahead
                       of the tiles (an expert too wide for the kernel's
                       VMEM goes through in slices of its width, a call a
                       slice: ``width_slices()``); ``silu(a) * g`` never
                       reaches HBM and the pair's routing weight is applied
                       in float32 before the one store. A tile past the last
                       group's end is neither fetched nor multiplied, an
                       expert without a row is never read: a pad row's
                       picks (sent to no expert's id by the model) and a
                       pick that lands on another chip cost nothing. Off a
                       TPU, under a serving mesh and at shapes the kernel
                       does not take (``groups_in_kernel()``) it is three
                       ``lax.ragged_dot``, which cost by the rows of their
                       operand whatever the group sizes say
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

``routed_ffn()``       a layer's routed experts from its normed rows: ``route()``,
                       a prefill's pad rows sent to no expert, then
                       ``grouped_experts()`` (a prefill) or
                       ``decode_experts()`` (a step) and their counts: the
                       one glue the expert families' layers call between
                       their own norms, shared expert and residual

All three return float32 sums over a row's picks (beside their counts); the
caller casts.
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
# in groups (``lax.map``), so that the sorted copy of the rows and the
# weighted products stay a few hundred MB beside a full chip (134 + 134 MB
# at 4096 rows x 8 picks in bfloat16). There the grouped kernel (0.41
# TFLOP a layer at Trinity-Mini's widths) takes 4.3 ms, the layer's 1.6 GB
# of experts read once 2.0 (PERF.md section 6, PR 43)
GROUP_ROWS = 4096
# the grouped kernel's row tile (``row_tile()``), and a share's room of
# pairs is an ODD number of them. The odd number is for the dots, which
# still run a share's room off the kernel's shapes: the compiler's grouped
# matmul cuts its rows into tiles of the largest of 512, 256, 128 that
# divides them and does a tile's work once for every group that touches it
# (12,928 rows 0.78 ms a dot, 12,800 rows 1.07: PERF.md section 6, PR 39).
# The kernel takes tiles of 128 whatever the room; the rule stays so that a
# room, and ``moe_prefill_pairs_moved``, are what they were
ROOM_TILE = 128
# the rows whose sums one matrix of the way back makes: a block of 128 owns
# at most 128 k products, so its matrix is [128, 128 k]
BAND_ROWS = 128
# the decode kernel's slice of an expert's width: W1 and W3 blocks [D, TF],
# W2 [TF, D], 2 MB each at D = 2048 in bfloat16, two buffers apiece
DECODE_TF = 512
DECODE_VMEM_BYTES = 48 << 20
# the grouped kernel's ``vmem_limit_bytes``: it holds an expert's three
# matrices (12.6 MB at 2048 x 1024 in bfloat16), two slots apiece, beside a
# row tile and its products; an expert whose whole width does not fit goes
# through in slices of it (``width_slices()``)
GROUPED_VMEM_BYTES = 64 << 20
# what ``grouped_experts()`` counts, in order
GROUPED_COUNTS = ("pairs_moved", "tile_rows")


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


def row_tile(pairs: int) -> int:
    """The rows of one tile of the grouped kernel: 128 (the matrix unit's
    rows) where that divides ``pairs``, else the largest of 64, 32, 16 that
    does; 0: no such tile, the rows are the dots'. A tile is worked whole
    for every group that touches it, so a smaller tile works fewer rows
    and a larger one makes fewer visits: on the chip tiles of 128 took
    least at every shape of the three cells, tiles of 64 and of 256 a
    tenth to a third more from 128 rows an expert on and the same below
    (PERF.md section 6, PR 43)."""
    for tm in (ROOM_TILE, 64, 32, 16):
        if pairs % tm == 0:
            return tm
    return 0


def width_slices(w1_shape, dtype) -> int:
    """The calls of the grouped kernel an expert stack [E, D, F] takes: 1
    where an expert's whole width fits the kernel's ``GROUPED_VMEM_BYTES``,
    else the fewest equal slices of the width, each whole registers of 128
    columns, that do; 0: none does. What a call of ``n`` columns takes: the
    three matrices' slices, two slots apiece, the row tile and the block of
    the product, two buffers apiece (the product float32 where sliced), the
    tile's float32 product and its two float32 halves and ``h``. Mosaic
    counts the slots, the buffered blocks and ~1.5 MiB of its own, 3 MiB
    under this sum at D = 4096; compiled for a described v5e at D = 4096: a
    width of 1152 fits whole (61.4 MiB by this sum) and 1280 does not
    ("Scoped allocation with size 65.56M and limit 64.00M"; 67.5 by this
    sum), 2048 does not ("96.00M") and goes in two slices of 1024 (57.3)."""
    _, d, width = w1_shape
    item = jnp.dtype(dtype).itemsize
    tm = ROOM_TILE
    for slices in range(1, width // 128 + 1):
        n = width // slices
        if width % slices or n % 128:
            continue
        out = item if slices == 1 else 4
        if (2 * 3 * d * n * item + 2 * tm * d * (item + out) + tm * d * 4
                + tm * n * (8 + item)) <= GROUPED_VMEM_BYTES:
            return slices
    return 0


def groups_in_kernel(platform, xs_shape, w1_shape, dtype) -> bool:
    """Whether the sorted pairs' SwiGLU, lowered for ``platform``, can be
    ``grouped_swiglu()``: a TPU, bfloat16 rows, widths that fill lanes,
    pairs that cut into tiles, and an expert of which some slice of the
    width fits the kernel's VMEM (``width_slices()``)."""
    return (
        platform == "tpu"
        and jnp.dtype(dtype) == jnp.bfloat16
        and xs_shape[1] % 128 == 0 and w1_shape[2] % 128 == 0
        and row_tile(xs_shape[0]) > 0
        and width_slices(w1_shape, dtype) > 0
    )


def tile_visits(sizes, pairs: int, tm: int):
    """The grouped kernel's walk over sorted pairs cut into tiles of ``tm``
    rows: group ``e`` (``sizes[e]`` rows from ``sum(sizes[:e])`` on) is
    worked once in every tile that holds a row of it, the groups in order
    and a group's tiles in order, so neither a tile nor an expert comes
    back once left. Returns ``(offsets [E + 1], ids [E], turn [V], tile
    [V], counts [2])``: ``counts`` is (visits ``n``, groups that have a
    row); ``ids`` holds those groups' experts first, ascending; visit ``v``
    works tile ``tile[v]`` for the group whose turn it is, ``ids[turn[v]]``
    (``V = pairs / tm + min(E, pairs) - 1`` is the most visits there can
    be; past ``n`` both arrays repeat the last visit's, so a block index
    read from them does not move). A group of no rows has no visit, and a
    tile past the last group's end has none either.

    Written in ``lax`` primitives, as the kernel's body is: every ``jnp``
    operator is a ``jit`` of its own, traced anew in every prefill
    executable of every process, and a benchmark run logs each such trace
    (``JAX_LOG_COMPILES``): as ``jnp`` this walk and the body cost a
    prefill executable 0.3 s of tracing and ``trinity-mini.longbatch`` 8%
    of its ``setup_s`` (PERF.md section 6, PR 43)."""
    n_experts = sizes.shape[0]
    i32 = jnp.int32
    sizes = lax.convert_element_type(sizes, i32)
    ends = lax.cumsum(sizes)
    first = lax.div(lax.sub(ends, sizes), i32(tm))        # a group's first tile
    has = lax.gt(sizes, i32(0))
    tiles = lax.select(
        has, lax.sub(lax.div(lax.add(ends, i32(tm - 1)), i32(tm)), first),
        lax.full_like(sizes, 0))
    upto = lax.cumsum(tiles)                              # visits through e
    before = lax.sub(upto, tiles)
    place = lax.sub(lax.cumsum(lax.convert_element_type(has, i32)), i32(1))
    last = lambda a: lax.index_in_dim(a, n_experts - 1, keepdims=False)  # noqa: E731
    n = last(upto)
    visits = pairs // tm + min(n_experts, pairs) - 1
    v = lax.min(lax.iota(i32, visits), lax.max(lax.sub(n, i32(1)), i32(0)))

    def of(rows: int, a, axis: int):
        return lax.broadcast_in_dim(a, (rows, n_experts), (axis,))

    def picked(rows: int, where, a):
        """[rows]: ``a[e]`` of the one ``e`` that ``where[row, e]`` marks."""
        return lax.reduce(
            lax.select(where, of(rows, a, 1), jnp.zeros(where.shape, i32)),
            i32(0), lax.add, (1,))

    # visit v is group e's where before[e] <= v < upto[e]
    col = of(visits, v, 0)
    mine = lax.bitwise_and(lax.ge(col, of(visits, before, 1)),
                           lax.lt(col, of(visits, upto, 1)))
    tile = lax.min(lax.add(v, picked(visits, mine, lax.sub(first, before))),
                   i32(pairs // tm - 1))
    e = lax.iota(i32, n_experts)
    ids = picked(n_experts, lax.bitwise_and(
        of(n_experts, has, 1),
        lax.eq(of(n_experts, place, 1), of(n_experts, e, 0))), e)
    one = lambda a: lax.reshape(a, (1,))  # noqa: E731
    return (lax.concatenate([jnp.zeros((1,), i32), ends], 0), ids,
            picked(visits, mine, place), tile,
            lax.concatenate([one(n), one(lax.add(last(place), i32(1)))], 0))


def _grouped_kernel(offs_ref, ids_ref, turn_ref, tile_ref, counts_ref, x_ref,
                    wt_ref, w1_hbm, w3_hbm, w2_hbm, y_ref, w1_buf, w3_buf,
                    w2_buf, sem, part=None):
    """Grid (V,): one visit. The experts' matrices stay in HBM; ``w*_buf``
    are VMEM [2, ...], two slots apiece, ``sem`` their DMA semaphores [3,
    2]. At a group's first visit its three matrices are waited for (the
    first group's are started there) and the NEXT group's start into the
    other slot, which the group before has done with: their copy runs
    beside ALL of this group's tiles, not beside its last one. Then the
    visit's row tile goes through the group's SwiGLU, ``W1`` and ``W3``
    side by side, ``h`` rounded once and never in HBM, each row weighted by
    its pair's routing weight in float32, and the group's rows of the tile
    are stored; the other rows keep what an earlier visit of the tile
    stored. Past the ``n`` visits the row tile's index stays where it was:
    nothing more is copied, and nothing is computed. (``lax`` primitives:
    ``tile_visits()`` says why.)

    ``part = (first, n)`` (static): this call works columns ``first ..
    first + n - 1`` of every expert's width alone (``W1`` and ``W3`` [D, n],
    ``W2`` [n, D], copied from where they lie in the whole matrices), and
    ``y_ref`` is that slice's share of the product, float32 (``_swiglu()``
    sums the slices)."""
    i32 = jnp.int32
    v = pl.program_id(0)
    tile = tile_ref[v]

    def copies(turn, slot):
        if part is None:
            srcs = [hbm.at[ids_ref[turn]] for hbm in (w1_hbm, w3_hbm, w2_hbm)]
        else:
            e, cols = ids_ref[turn], pl.ds(*part)
            srcs = [w1_hbm.at[e, :, cols], w3_hbm.at[e, :, cols],
                    w2_hbm.at[e, cols, :]]
        return [
            pltpu.make_async_copy(src, buf.at[slot], sem.at[i, slot])
            for i, (src, buf) in enumerate(zip(srcs, (w1_buf, w3_buf, w2_buf)))]

    @pl.when(lax.lt(v, counts_ref[0]))
    def _():
        turn = turn_ref[v]
        slot = lax.rem(turn, i32(2))
        g = ids_ref[turn]
        opens = lax.eq(v, i32(0))

        @pl.when(opens)
        def _():
            for c in copies(turn, slot):
                c.start()

        @pl.when(lax.bitwise_or(
            opens, lax.ne(turn_ref[lax.max(lax.sub(v, i32(1)), i32(0))], turn)))
        def _():
            for c in copies(turn, slot):
                c.wait()
            after = lax.add(turn, i32(1))

            @pl.when(lax.lt(after, counts_ref[1]))
            def _():
                for c in copies(after, lax.sub(i32(1), slot)):
                    c.start()

        x = x_ref[...]
        a = jnp.dot(x, w1_buf[slot], preferred_element_type=jnp.float32)
        b = jnp.dot(x, w3_buf[slot], preferred_element_type=jnp.float32)
        h = lax.mul(lax.mul(a, lax.logistic(a)), b).astype(x.dtype)  # silu(a) b
        y = jnp.dot(h, w2_buf[slot], preferred_element_type=jnp.float32)
        y = (y * wt_ref[...]).astype(y_ref.dtype)
        rows = lax.add(lax.mul(tile, i32(y.shape[0])),
                       lax.broadcasted_iota(i32, y.shape, 0))
        own = lax.bitwise_and(lax.ge(rows, offs_ref[g]),
                              lax.lt(rows, offs_ref[lax.add(g, i32(1))]))
        y_ref[...] = lax.select(own, y, y_ref[...])


def _swiglu_call(xs, walk, by_pair, w1, w3, w2, tm: int, interpret: bool,
                 part=None):
    """One call of the grouped kernel over a walk already made
    (``tile_visits()``): the experts' whole width, or with ``part = (first,
    n)`` those columns of it, in float32."""
    m, d = xs.shape
    width = w1.shape[2] if part is None else part[1]
    rows = lambda v, offs, ids, turn, tile, counts: (tile[v], 0)  # noqa: E731
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        _grouped_kernel if part is None
        else functools.partial(_grouped_kernel, part=part),
        out_shape=jax.ShapeDtypeStruct(
            (m, d), xs.dtype if part is None else jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(walk[3].shape[0],),
            in_specs=[pl.BlockSpec((tm, d), rows), pl.BlockSpec((tm, 1), rows),
                      hbm, hbm, hbm],
            out_specs=pl.BlockSpec((tm, d), rows),
            scratch_shapes=[
                pltpu.VMEM((2, d, width), w1.dtype),
                pltpu.VMEM((2, d, width), w3.dtype),
                pltpu.VMEM((2, width, d), w2.dtype),
                pltpu.SemaphoreType.DMA((3, 2)),
            ],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=GROUPED_VMEM_BYTES,
        ),
        name="grouped_swiglu",
        interpret=interpret,
    )(*walk, xs, by_pair.astype(jnp.float32)[:, None], w1, w3, w2)


def _swiglu(xs, walk, by_pair, w1, w3, w2, tm: int, interpret: bool):
    """``grouped_swiglu()`` over a walk already made: one call of the
    kernel where an expert's whole width fits it, else one a slice of the
    width (``width_slices()``), the slices' float32 shares summed and
    rounded once, as the one call rounds its product."""
    slices = width_slices(w1.shape, w1.dtype)
    if slices == 1:
        return _swiglu_call(xs, walk, by_pair, w1, w3, w2, tm, interpret)
    n = w1.shape[2] // slices
    return sum(
        _swiglu_call(xs, walk, by_pair, w1, w3, w2, tm, interpret, (s * n, n))
        for s in range(slices)).astype(xs.dtype)


@functools.partial(jax.jit, static_argnames=("tm", "interpret"))
def grouped_swiglu(xs, sizes, by_pair, w1, w3, w2, tm: int = 0,
                   interpret: bool = False):
    """The grouped kernel itself, one call. xs [M, D]: the pairs' rows
    sorted by expert; ``sizes`` [E] int32: the rows of each expert's group,
    one after the other from row 0 (rows past their sum belong to no
    group); ``by_pair`` [M] float32: each pair's routing weight; the
    stacked experts, left in HBM. Returns [M, D] in xs's dtype: ``weight x
    (silu(x W1) * (x W3)) W2`` of each row's own expert, ``h`` rounded to
    xs's dtype once and the weighted product once; a row of no group holds
    nothing that was computed. ``tm``: ``row_tile()`` of the shapes where
    not given. The matrix unit is given ``visits x tm`` rows
    (``tile_visits()``), and an expert's three matrices are read once if it
    has a row and never if it has none."""
    m = xs.shape[0]
    tm = tm or row_tile(m)
    if not tm or m % tm:
        raise ValueError(f"{m} pairs do not cut into tiles of {tm} rows")
    return _swiglu(xs, tile_visits(sizes, m, tm), by_pair, w1, w3, w2, tm,
                   interpret)


def _pairs_ffn(xs, sizes, real, by_pair, w1, w3, w2, kernel: bool = True):
    """The experts' SwiGLU over pairs sorted by expert, and the rows of
    tiles it worked. xs [M, D]; ``sizes`` [E]: the groups, one after the
    other from row 0; ``real`` [M] bool: the rows that belong to a group
    (the first ``sum(sizes)``); ``by_pair`` [M] float32. Returns ``(y [M,
    D] in xs's dtype, zeros where not real, tile rows)``: on a TPU
    ``grouped_swiglu()``, whose work follows the groups (``visits x tile``
    rows); off one, at shapes the kernel does not take and without
    ``kernel`` (a serving mesh: Mosaic kernels are not partitioned; a
    decode step's few rows), three ``lax.ragged_dot``, which cost by the M
    rows of their operand whatever the sizes say."""
    m = xs.shape[0]

    def dots(xs, sizes, real, by_pair, w1, w3, w2, *walk):
        a = lax.ragged_dot(xs, w1, sizes, preferred_element_type=jnp.float32)
        g = lax.ragged_dot(xs, w3, sizes, preferred_element_type=jnp.float32)
        h = (jax.nn.silu(a) * g).astype(xs.dtype)
        y = lax.ragged_dot(h, w2, sizes, preferred_element_type=jnp.float32)
        # rows past the last group were given to no expert: whatever lies
        # there is not a product
        y = jnp.where(real[:, None], y, 0.0)
        return (y * by_pair[:, None]).astype(xs.dtype)

    args = (xs, sizes, real, by_pair, w1, w3, w2)
    if not (kernel and groups_in_kernel("tpu", xs.shape, w1.shape, xs.dtype)):
        return dots(*args), jnp.int32(m)
    tm = row_tile(m)
    walk = tile_visits(sizes, m, tm)

    def grouped(xs, sizes, real, by_pair, w1, w3, w2, *walk):
        y = _swiglu(xs, walk, by_pair, w1, w3, w2, tm, False)
        return jnp.where(real[:, None], y, jnp.zeros((), y.dtype))

    # tile rows: counted beside the call, from what the kernel is told
    return (lax.platform_dependent(*args, *walk, tpu=grouped, default=dots),
            walk[4][0] * tm)


def _grouped(x, picks, weights, w1, w3, w2, kernel: bool = True):
    """One group of rows through every expert the stacks hold: the (row,
    pick) pairs sorted by expert, ``_pairs_ffn()``, unsorted, a row's
    picks summed in float32. A pick of ``n_experts`` or more is no
    expert's (a pad row's, ``localise()``'s): it sorts last, joins no
    group and adds nothing. Returns ``(float32 [N, D], tile rows)``."""
    n, d = x.shape
    k = picks.shape[1]
    n_experts = w1.shape[0]
    flat = picks.reshape(-1)
    order = jnp.argsort(flat, stable=True)
    xs = x[order // k]                                   # [N k, D] by expert
    sizes = jnp.sum(
        flat[:, None] == jnp.arange(n_experts, dtype=flat.dtype)[None, :],
        axis=0, dtype=jnp.int32)
    y, worked = _pairs_ffn(xs, sizes, flat[order] < n_experts,
                           weights.reshape(-1)[order], w1, w3, w2, kernel)
    back = jnp.argsort(order)                             # the unsort
    return y[back].reshape(n, k, d).astype(jnp.float32).sum(axis=1), worked


def room_of(pairs: int, held, n_routed: int) -> int:
    """The (row, pick) pairs a share's grouped path moves in one pass: what
    ``pairs`` uniform picks over ``n_routed`` experts send to the ``held[1]``
    held ones and a quarter more (5/16 of all pairs where a chip holds a
    quarter of the layer), rounded up to an odd number of ``ROOM_TILE``;
    never more than there are."""
    expected = -(-pairs * held[1] * 5 // (n_routed * 4))
    return min(pairs, (-(-expected // ROOM_TILE) | 1) * ROOM_TILE)


def _grouped_held(x, picks, weights, w1, w3, w2, room: int,
                  kernel: bool = True):
    """``_grouped()`` over ``room`` pairs at a time. picks [N,
    k] are local already (``localise()``): after the sort the ``c`` pairs
    that landed here are the first ``c`` of the order, and pass ``p`` of
    ``ceil(c / room)`` takes those from ``p room`` on: gathers their rows,
    multiplies them (each pass's groups are the experts' groups cut to its
    window), weights them, and adds them to their rows' sums. A pair that
    landed elsewhere is never gathered, and every pair that landed here
    is in some pass. Returns ``(float32 [N, D], pairs moved, tile rows)``.

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

    def one_pass(p, carry):
        out, worked = carry
        lo = p * room
        real = lo + jnp.arange(room, dtype=jnp.int32) < landed
        pair = lax.dynamic_slice(order, (lo,), (room,))
        xs = x[pair // k]                                 # [room, D] by expert
        here = (jnp.clip(ends, lo, lo + room)
                - jnp.clip(ends - sizes, lo, lo + room))
        y, tiles = _pairs_ffn(xs, here, real, by_pair[pair], w1, w3, w2, kernel)
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
            lambda r: rows_sum(*r), (firsts, start)).reshape(n, d), worked + tiles

    with jax.named_scope("held_experts_prefill"):
        out, worked = lax.fori_loop(
            0, passes, one_pass,
            (jnp.zeros((n, d), jnp.float32), jnp.int32(0)))
    return out, passes * room, worked


@functools.partial(jax.jit,
                   static_argnames=("group_rows", "held", "n_routed", "mesh"))
def grouped_experts(x, picks, weights, w1, w3, w2, group_rows: int = GROUP_ROWS,
                    held=None, n_routed=None, mesh=None):
    """x [N, D] rows, picks / weights [N, k] (``route()``), the stacked
    experts -> ``(float32 [N, D], counts)``: the sum over a row's picks of
    weight x expert(x), and int32 ``[pairs moved, tile rows]``
    (``GROUPED_COUNTS``). Drop-free whatever the picks; a pick that is no
    held expert's id (a pad row's, sent to ``n_routed``) adds nothing and
    costs the kernel nothing. More than ``group_rows`` rows go through in
    equal groups of at most that many. ``held=(lo, n)``: the stacks are
    experts ``lo .. lo + n - 1`` of the ``n_routed`` the picks range over,
    a pick outside them adds nothing (``localise()``) and is not moved
    either: a group's pairs go through ``room_of()`` at a time, and the
    pairs moved are ``room x passes`` summed over the groups (without
    ``held``: every pair). Tile rows: what ``_pairs_ffn()`` worked. Under
    a serving ``mesh`` the rows are the dots'."""
    n = x.shape[0]
    groups = -(-n // group_rows)
    while n % groups:
        groups += 1
    if held is None:
        def one(x, picks, weights):
            y, worked = _grouped(x, picks, weights, w1, w3, w2, mesh is None)
            return y, jnp.stack([jnp.int32(picks.size), worked])
    else:
        if not n_routed:
            raise ValueError(f"held {held} of how many experts: n_routed")
        picks, weights = localise(picks, weights, held, w1.shape[0])
        room = room_of(n // groups * picks.shape[1], held, n_routed)

        def one(x, picks, weights):
            y, moved, worked = _grouped_held(
                x, picks, weights, w1, w3, w2, room, mesh is None)
            return y, jnp.stack([moved, worked])
    if groups == 1:
        return one(x, picks, weights)
    split = lambda a: a.reshape(groups, n // groups, *a.shape[1:])  # noqa: E731
    y, counts = lax.map(
        lambda r: one(*r), (split(x), split(picks), split(weights)))
    return y.reshape(n, x.shape[1]), counts.sum(axis=0)


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
        # the picks are local already; a step's few rows are the dots'
        return _grouped(x, picks, weights, w1, w3, w2, kernel=False)[0]

    args = (x, picks, weights, ids, n, w1, w3, w2)
    if not decodes_touched("tpu", x.shape, w1.shape, mesh):
        return grouped(*args), n, routed
    return lax.platform_dependent(*args, tpu=kernel, default=grouped), n, routed


def routed_ffn(rows, router, bias, k: int, scale: float, score: str, stacks,
               *, live, real, held, n_routed: int, mesh, redirect_pads: bool):
    """A layer's routed experts over its normed rows [N, D]: ``route()``
    by ``router``, ``bias``, ``k``, ``scale``, ``score``, then the ``stacks``
    (``w1``, ``w3``, ``w2``; the chip's share where ``held`` is given, of the
    ``n_routed`` the router ranges over). Returns ``(float32 [N, D], picks
    [N, k] over ALL experts, counts)``.

    ``live`` None, a prefill: ``grouped_experts()`` and its
    ``GROUPED_COUNTS``. Of the rows ``real`` (bool, or None: all) says are no
    sequence's tokens, the picks go to ``n_routed``, no expert's id, where
    ``redirect_pads``: padding computes nothing that is read, and it routes
    together, so a bucket's worth of it would overflow a share's room (PR
    39). The family says: always (afmoe), or under ``held`` (the shares).

    ``live`` [N] bool, a decode step: ``decode_experts()`` and ``(experts
    touched, rows routed, rows landed on a held expert)``, idle lanes in none."""
    picks, weights = route(rows, router, bias, k, scale, score=score)
    if live is None:
        sent = picks
        if real is not None and redirect_pads:
            sent = jnp.where(real.reshape(-1, 1), picks, n_routed)
        y, counts = grouped_experts(rows, sent, weights, *stacks, held=held,
                                    n_routed=n_routed, mesh=mesh)
    else:
        y, touched, routed = decode_experts(rows, picks, weights, live, *stacks,
                                            mesh=mesh, held=held)
        lo, n = held or (0, n_routed)
        here = (picks >= lo) & (picks < lo + n) & live[:, None]
        counts = (touched, routed, here.sum(dtype=jnp.int32))
    return y, picks, counts
