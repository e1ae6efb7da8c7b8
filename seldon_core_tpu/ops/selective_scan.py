"""The Mamba-1 selective scan for serving: a prefill over whole prompts that
stops each sequence's state at its own last token, and a one-token decode
step that reads and writes the state of live lanes only.

A selective-state-space layer keeps, for each of its ``C`` channels, ``N``
numbers in float32 (``S`` [N, C] here: the state's ``N`` along sublanes,
the channels along lanes; the published layout is its transpose, [C, N],
whose 16-wide rows would fill an eighth of a lane tile), zero at a
sequence's start. A token with input ``x`` [C] (the convolution's output),
step ``delta`` [C] > 0, ``b``, ``c`` [N] does

    S <- exp(delta * A) * S + b[:, None] * (delta * x)[None, :]
    y = sum_n c[n] S[n] + D * x

with ``A`` [N, C] < 0 and ``D`` [C] the layer's own. The decay is a
different number for every (state, channel) and token, so no chunk of the
recurrence is a matrix product: the prefill is a scan on the vector unit,
and the decode step is the state's traffic.

``selective_scan_prefill()``  whole prompts. On a TPU one Pallas kernel,
                      grid (sequence, chunk of ``CHUNK`` positions): the
                      state [N, C] stays in VMEM from a sequence's first
                      chunk to its last and goes out once; inside a chunk
                      the channels go ``WIDTH`` at a time, a group's state
                      in registers across the chunk's steps. Steps at or
                      past ``lens`` are neither fetched (the index maps
                      park on the last chunk a sequence has) nor computed,
                      and the steps of a sequence's last chunk past its
                      length take ``delta = 0``: ``exp(0) S + 0`` is ``S``,
                      so what goes out IS the state after the sequence's
                      last real token, whatever bucket it was padded to.
                      Elsewhere, and as the kernel's oracle, a ``lax.scan``
                      over time under the same mask
``selective_scan_step()``  one token a lane over the state of EVERY layer
                      of the kind, [lanes, layers, N, C] (the cache's one
                      array, so that a scan over layers carries it whole
                      and nothing is sliced out or stacked back): on a TPU
                      a Pallas kernel whose grid walks the lanes in groups
                      of ``LANE_GROUP`` (scalars prefetched:
                      ``lanes_walked``); a program whose group holds a
                      live lane copies the group's state of layer
                      ``layer`` in, updates it in VMEM and copies it out,
                      once; ``x`` and ``delta`` come in as they lie,
                      [lanes, C] in the served dtype, and ``y`` goes out
                      so. The state is aliased in and out: an idle lane's
                      goes back as it came, a group's without a live lane
                      and every other layer's is neither read nor written.
                      Elsewhere the same arithmetic in ``jax.numpy`` under
                      a mask
``conv_tail_step()``  the convolution in front of it, one token a lane
                      over the tails of every layer of the kind, [lanes,
                      layers, K - 1, C]: on a TPU a Pallas kernel that
                      reads the layer's tails and this token's ``a`` where
                      they lie, forms the window's product, the bias and
                      the SiLU, hands ``c`` out and writes the live
                      lanes' shifted tails back in place. Elsewhere
                      ``ops.gated_delta.conv_step`` on the layer's slice

Operands (``x``, ``delta``, ``b``, ``c``) are the served dtype; the state,
the decay and the recurrence are float32.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# positions a program of the prefill kernel walks: x, delta and y in blocks
# of [CHUNK, C], b and c in blocks of [CHUNK, N, 128] float32 (4 MB of VMEM
# with their second buffers at C = 5120)
CHUNK = 64
# steps the prefill kernel loads at once: a bfloat16 tile's 16 rows
ROWS = 16
# channels whose state [N, WIDTH] float32 stays in registers across a
# chunk's steps (8 registers at N = 16, beside as many of A and twice as
# many of the 16 steps' x and delta)
WIDTH = 512
LANES = 128
# lanes a program of the step's state kernel holds: their states [8, N, C]
# float32 in and out with their second buffers (10.5 MB of VMEM at N = 16,
# C = 5120), their operands' rows a sublane tile
LANE_GROUP = 8
# lanes a program of the tails kernel holds, the first of these that divides
# the lanes: whole bfloat16 tiles of 16 rows (two: 0.98 MB a block of three
# taps at C = 5120)
TAIL_LANES = (32, 16)


def _wide(a):
    """a [..., N] -> [..., N, 128] float32, each value repeated along the
    lanes: a column the kernels multiply a [N, 128 m] tile by without a
    transposition (N moves from the lanes to the sublanes here, in XLA)."""
    return jnp.broadcast_to(a.astype(jnp.float32)[..., None],
                            (*a.shape, LANES))


def _tiled(col, width):
    """col [N, 128] -> [N, width]."""
    return jnp.concatenate([col] * (width // LANES), axis=1)


# -- prefill -------------------------------------------------------------------------

def _prefill_scanned(x, delta, b, c, a, d, lens):
    """``selective_scan_prefill`` off a TPU, and the kernel's oracle: the
    recurrence as written, one ``lax.scan`` over time."""
    f32 = jnp.float32
    bsz, t, _ = x.shape
    real = jnp.arange(t, dtype=jnp.int32)[None, :] < lens[:, None]

    def step(s, xs):
        x_t, dt_t, b_t, c_t, real_t = xs
        x_t, b_t, c_t = x_t.astype(f32), b_t.astype(f32), c_t.astype(f32)
        # a step at or past the length neither decays nor writes
        dt_t = jnp.where(real_t[:, None], dt_t.astype(f32), 0.0)
        s = jnp.exp(dt_t[:, None, :] * a) * s + (
            b_t[:, :, None] * (dt_t * x_t)[:, None, :])
        y = jnp.sum(s * c_t[:, :, None], axis=1) + d * x_t
        return s, y.astype(x.dtype)

    with jax.named_scope("selective_scan_prefill"):
        s, y = lax.scan(
            step, jnp.zeros((bsz, *a.shape), f32),
            tuple(jnp.moveaxis(v, 1, 0) for v in (x, delta, b, c, real)))
    return jnp.moveaxis(y, 0, 1), s


def _prefill_kernel(lens_ref, x_ref, dt_ref, b_ref, c_ref, a_ref, d_ref,
                    y_ref, s_ref, *, width):
    """Grid (B, chunks), the chunks in turn: program (i, n) is chunk n of
    sequence i. ``s_ref`` [1, N, C] float32 is the sequence's state: its
    block index does not move with n, so it stays in VMEM from the first
    chunk to the last and goes out once. Refs: x, delta, y [1, CHUNK, C];
    b, c [1, CHUNK, N, 128] float32 (``_wide``); a [N, C], d [1, C]
    float32; ``lens_ref`` SMEM [B]. At or past ``ceil(lens[i] / CHUNK)`` the
    index maps park on the last block fetched and the program writes zeros
    to the chunk's outputs, nothing else."""
    n = pl.program_id(1)
    chunk, channels = x_ref.shape[1:]
    length = lens_ref[pl.program_id(0)]
    f32 = jnp.float32

    @pl.when(n == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    # the rows no step below writes go out as zeros, not as what the buffer
    # held: a row past a sequence's length is of no use but is read (it is a
    # padded position's input to the next layer)
    @pl.when((n + 1) * chunk > length)
    def _():
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(n * chunk < length)
    def _():
        here = jnp.minimum(length - n * chunk, chunk)
        blocks = (here + ROWS - 1) // ROWS
        row = lax.broadcasted_iota(jnp.int32, (ROWS, 1), 0)

        def group(g, _):
            cols = pl.ds(pl.multiple_of(g * width, width), width)
            a = a_ref[:, cols]                                      # [N, width]
            d = d_ref[:, cols]                                      # [1, width]

            def block(tb, s):
                rows = pl.ds(pl.multiple_of(tb * ROWS, ROWS), ROWS)
                x = x_ref[0, rows, cols].astype(f32)             # [ROWS, width]
                # a step at or past the length neither decays nor writes
                dt = jnp.where(tb * ROWS + row < here,
                               dt_ref[0, rows, cols].astype(f32), 0.0)
                dx = dt * x
                ys = []
                for i in range(ROWS):
                    t = tb * ROWS + i
                    s = jnp.exp(dt[i:i + 1] * a) * s + (
                        _tiled(b_ref[0, t], width) * dx[i:i + 1])
                    ys.append(jnp.sum(s * _tiled(c_ref[0, t], width), axis=0,
                                      keepdims=True))
                y_ref[0, rows, cols] = (
                    jnp.concatenate(ys, axis=0) + d * x).astype(y_ref.dtype)
                return s

            s_ref[0, :, cols] = lax.fori_loop(0, blocks, block,
                                              s_ref[0, :, cols])

        lax.fori_loop(0, channels // width, group, None)


@functools.partial(jax.jit, static_argnames=("interpret",))
def selective_scan_prefill_kernel(x, delta, b, c, a, d, lens,
                                  interpret: bool = False):
    """The prefill kernel itself, ``selective_scan_prefill``'s arguments and
    results. x, delta go in as they lie, [B, T, C]; b and c as [B, T, N,
    128] float32. Chunks walked: ``ceil(lens / CHUNK)`` a sequence; the
    outputs of the others are zeros."""
    bsz, t, channels = x.shape
    n_state = a.shape[0]
    if not prefills_in_kernel("tpu", x.shape, a.shape):
        raise ValueError(f"x {x.shape}, A {a.shape} do not fit the kernel")
    width = min(WIDTH, channels)
    pad = -t % CHUNK
    n = (t + pad) // CHUNK
    lens = lens.astype(jnp.int32)

    def padded(v):
        return jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))

    def walked(n_, lens):
        """The chunk program (., n_) reads: its own while the sequence has
        it, then the last one it had."""
        return jnp.minimum(n_, jnp.maximum(pl.cdiv(lens, CHUNK) - 1, 0))

    rows = pl.BlockSpec((1, CHUNK, channels),
                        lambda i, n_, lens: (i, walked(n_, lens[i]), 0))
    cols = pl.BlockSpec((1, CHUNK, n_state, LANES),
                        lambda i, n_, lens: (i, walked(n_, lens[i]), 0, 0))
    y, s = pl.pallas_call(
        functools.partial(_prefill_kernel, width=width),
        out_shape=(jax.ShapeDtypeStruct((bsz, t + pad, channels), x.dtype),
                   jax.ShapeDtypeStruct((bsz, n_state, channels), jnp.float32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bsz, n),
            in_specs=[rows, rows, cols, cols,
                      pl.BlockSpec((n_state, channels), lambda i, n_, lens: (0, 0)),
                      pl.BlockSpec((1, channels), lambda i, n_, lens: (0, 0))],
            out_specs=(
                pl.BlockSpec((1, CHUNK, channels), lambda i, n_, lens: (i, n_, 0)),
                pl.BlockSpec((1, n_state, channels), lambda i, n_, lens: (i, 0, 0))),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=48 << 20),
        name="selective_scan_prefill",
        interpret=interpret,
    )(lens, padded(x), padded(delta), padded(_wide(b)), padded(_wide(c)),
      a.astype(jnp.float32), d.astype(jnp.float32).reshape(1, channels))
    return y[:, :t], s


def prefills_in_kernel(platform, x_shape, a_shape, mesh=None) -> bool:
    """Whether ``selective_scan_prefill()``, lowered for ``platform``, is the
    kernel: a TPU, no serving mesh (Mosaic kernels are not partitioned), a
    state whose ``N`` is whole sublane tiles and channels in whole groups of
    lanes."""
    channels = x_shape[2]
    return (platform == "tpu" and mesh is None and a_shape[0] % 8 == 0
            and channels % LANES == 0
            and channels % min(WIDTH, channels) == 0)


@functools.partial(jax.jit, static_argnames=("mesh",))
def selective_scan_prefill(x, delta, b, c, a, d, lens, mesh=None):
    """Whole prompts. x [B, T, C] the convolution's output, delta [B, T, C]
    the step (after its softplus), b, c [B, T, N], in the served dtype; a
    [N, C] (negative), d [C] float32; lens [B] int32: a sequence's real
    tokens are its first ``lens``. Returns y [B, T, C] in x's dtype (the
    rows at or past ``lens`` are of no use: zeros from the kernel past a
    sequence's last 16 steps) and each sequence's state after its last real
    token [B, N, C] float32."""
    lens = lens.astype(jnp.int32)
    a, d = a.astype(jnp.float32), d.astype(jnp.float32)

    def kernel(*args):
        with jax.named_scope("selective_scan_prefill"):
            return selective_scan_prefill_kernel(*args)

    args = (x, delta, b, c, a, d, lens)
    if not prefills_in_kernel("tpu", x.shape, a.shape, mesh):
        return _prefill_scanned(*args)
    return lax.platform_dependent(*args, tpu=kernel, default=_prefill_scanned)


# -- the decode step -------------------------------------------------------------------

def _step_math(s, x, delta, b, c, a, d):
    """The recurrence for one token over a leading lane axis: s [B, N, C],
    x, delta [B, C], b, c [B, N], a [N, C], d [C]. All float32."""
    s = jnp.exp(delta[:, None, :] * a) * s + (
        b[:, :, None] * (delta * x)[:, None, :])
    return s, jnp.sum(s * c[:, :, None], axis=1) + d * x


class Walk(NamedTuple):
    """``lanes_walked``'s result."""
    groups: jax.Array     # [B / LANE_GROUP] int32
    live: jax.Array       # [B] int32
    column: jax.Array     # [B, 1] int32: ``live`` down the sublanes


def lanes_walked(live):
    """What the step's two kernels take of ``live`` [B] bool. It is the same
    for every layer of a step, so a caller that steps many layers computes
    it once and hands it to each call as ``walk``. ``groups[g]`` is the
    group of ``LANE_GROUP`` lanes whose state program g of the state kernel
    holds: g itself where one of its lanes is live, else the nearest such
    group before it (no block moves between two programs that hold one
    group), else the first there is; 0 where no lane is live at all, the
    one case in which a program holds its own group without a live lane in
    it. None where the lanes are no whole groups: the kernels are not the
    path there."""
    lanes = live.shape[0]
    if lanes % LANE_GROUP:
        return None
    mine = jnp.arange(lanes // LANE_GROUP, dtype=jnp.int32)
    some = live.reshape(-1, LANE_GROUP).any(axis=1)
    before = lax.cummax(jnp.where(some, mine, -1))
    groups = jnp.where(before < 0, jnp.argmax(some).astype(jnp.int32), before)
    flags = live.astype(jnp.int32)
    return Walk(groups, flags, flags[:, None])


def _columns(across, first, out_ref):
    """across [N, B] float32 (a lane's values down a column) -> ``out_ref``
    [G, N, 128]: lanes ``first .. first + G``, each lane's values down the
    sublanes and repeated along the lanes, which is how a [N, 128 m] tile
    of the state multiplies by them. A lane's column is picked by a mask
    and a sum along the lanes (one term of each sum is not zero): no
    transposition and no slice at a lane that is no constant."""
    lane = lax.broadcasted_iota(jnp.int32, across.shape, 1)
    for j in range(out_ref.shape[0]):
        col = jnp.sum(jnp.where(lane == first + j, across, 0.0), axis=1,
                      keepdims=True)
        out_ref[j] = jnp.broadcast_to(col, out_ref.shape[1:])


def _step_kernel(groups_ref, live_ref, _layer_ref, s_in, x_ref, dt_ref, b_ref,
                 c_ref, a_ref, d_ref, s_out, y_ref, b_cols, c_cols, *, width):
    """Grid (B / G,): program g steps lanes [g G, (g + 1) G) of the layer
    where one of them is live, and writes zeros to their ``y`` where none
    is: its state block is then the one the program before it held, which
    neither moves nor is touched. Refs: s [G, 1, N, C] float32; x, delta, y
    [G, C] as they lie; b, c [N, B] whole, as they lie; a [N, C], d [1, C];
    SMEM ``groups_ref`` [B / G], ``live_ref`` [B] (``lanes_walked``),
    ``_layer_ref`` [1] (the index maps'); scratch ``b_cols``, ``c_cols`` [G,
    N, 128] (``_columns``)."""
    g = pl.program_id(0)
    group, channels = x_ref.shape
    f32 = jnp.float32
    live = [live_ref[g * group + j] != 0 for j in range(group)]
    some = functools.reduce(jnp.logical_or, live)

    @pl.when(some)
    def _():
        _columns(b_ref[...].astype(f32), g * group, b_cols)
        _columns(c_ref[...].astype(f32), g * group, c_cols)

        def chunk(k, _):
            cols = pl.ds(pl.multiple_of(k * width, width), width)
            x = x_ref[:, cols].astype(f32)                        # [G, width]
            dt = dt_ref[:, cols].astype(f32)
            dx, a, d = dt * x, a_ref[:, cols], d_ref[:, cols]
            ys = []
            for j in range(group):
                old = s_in[j, 0, :, cols]                         # [N, width]
                s = jnp.exp(dt[j:j + 1] * a) * old + (
                    _tiled(b_cols[j], width) * dx[j:j + 1])
                # an idle lane beside a live one goes back as it came
                s_out[j, 0, :, cols] = jnp.where(live[j], s, old)
                y = jnp.sum(s * _tiled(c_cols[j], width), axis=0,
                            keepdims=True) + d * x[j:j + 1]
                ys.append(jnp.where(live[j], y, 0.0))
            y_ref[:, cols] = jnp.concatenate(ys, axis=0).astype(y_ref.dtype)

        lax.fori_loop(0, channels // width, chunk, None)

    @pl.when(jnp.logical_not(some))
    def _():
        y_ref[...] = jnp.zeros_like(y_ref)

    # its own group and no live lane in it: no lane is live at all, and the
    # one block every program maps to goes back as it came
    @pl.when(jnp.logical_not(some) & (groups_ref[g] == g))
    def _():
        s_out[...] = s_in[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def selective_scan_step_kernel(s, layer, x, delta, b, c, a, d, live,
                               walk=None, interpret: bool = False):
    """The decode kernel itself: s [B, L, N, C] float32 (aliased in and
    out), ``layer`` the one of the L that steps, x, delta [B, C] as they
    lie (cast inside), b, c [B, N] (a lane's column picked inside), a [N,
    C], d [C], live [B] bool, ``walk`` ``lanes_walked(live)`` -> ``(s, y [B,
    C] in x's dtype)``; an idle lane's ``y`` is zeros and its state is not
    touched, nor is any other layer's. HBM bytes moved: groups of
    ``LANE_GROUP`` lanes that hold a live lane x LANE_GROUP x N x C x 4 B,
    read once and written once; a group without one is not read."""
    bsz, _, n_state, channels = s.shape
    if not steps_in_kernel("tpu", s.shape):
        raise ValueError(f"state {s.shape} does not fit the kernel")
    width = min(WIDTH, channels)
    f32 = jnp.float32
    walk = lanes_walked(live) if walk is None else walk
    layer = jnp.asarray(layer, jnp.int32).reshape(1)

    rows = pl.BlockSpec((LANE_GROUP, channels),
                        lambda g, groups, *_: (groups[g], 0))
    across = pl.BlockSpec((n_state, bsz), lambda g, *_: (0, 0))
    state = pl.BlockSpec(
        (LANE_GROUP, 1, n_state, channels),
        lambda g, groups, live, layer: (groups[g], layer[0], 0, 0))
    return pl.pallas_call(
        functools.partial(_step_kernel, width=width),
        out_shape=(jax.ShapeDtypeStruct(s.shape, f32),
                   jax.ShapeDtypeStruct((bsz, channels), x.dtype)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(bsz // LANE_GROUP,),
            in_specs=[state, rows, rows, across, across,
                      pl.BlockSpec((n_state, channels), lambda g, *_: (0, 0)),
                      pl.BlockSpec((1, channels), lambda g, *_: (0, 0))],
            out_specs=(state, pl.BlockSpec((LANE_GROUP, channels),
                                           lambda g, *_: (g, 0))),
            scratch_shapes=[pltpu.VMEM((LANE_GROUP, n_state, LANES), f32)] * 2,
        ),
        # with the three scalars counted: the state is operand 3, result 0
        input_output_aliases={3: 0},
        # a group's state in and out with their second buffers: 10.5 MB at
        # N = 16, C = 5120
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=32 << 20),
        name="selective_scan_step",
        interpret=interpret,
    # b and c transposed: as [B, N] lies (the lanes minor), so nothing moves
    )(walk.groups, walk.live, layer, s, x, delta, b.T, c.T,
      a.astype(f32), d.astype(f32).reshape(1, channels))


def steps_in_kernel(platform, shape, mesh=None) -> bool:
    """Whether the decode step, lowered for ``platform``, is the kernels:
    ``selective_scan_step()`` over a state of ``shape`` [B, L, N, C],
    ``conv_tail_step()`` over tails of ``shape`` [B, L, K - 1, C]. A TPU, no
    serving mesh (Mosaic kernels are not partitioned), lanes in whole
    bfloat16 tiles of 16 rows (``TAIL_LANES``; groups of ``LANE_GROUP``
    with them) and channels in whole groups of lanes; N and K - 1 are whole
    axes of the kernels' blocks, any number."""
    lanes, _, _, channels = shape
    return (platform == "tpu" and mesh is None
            and lanes % TAIL_LANES[-1] == 0
            and channels % LANES == 0
            and channels % min(WIDTH, channels) == 0)


@functools.partial(jax.jit, static_argnames=("mesh",))
def selective_scan_step(s, layer, x, delta, b, c, a, d, live, mesh=None,
                        walk=None):
    """One token a lane in layer ``layer`` (int32, may be traced) of the
    state s [B, L, N, C] float32. x, delta [B, C], b, c [B, N] in the served
    dtype; a [N, C], d [C]; live [B] bool; ``walk``: ``lanes_walked(live)``
    where the caller has it. Returns ``(s, y [B, C] in x's dtype)``: a live
    lane's state of that layer after its token and the token's output (an
    idle lane's: zeros); every other lane's and layer's state as it was."""
    f32 = jnp.float32
    layer = jnp.asarray(layer, jnp.int32)

    def kernel(s, layer, x, delta, b, c, a, d, live, walk):
        return selective_scan_step_kernel(s, layer, x, delta, b, c, a, d, live,
                                          walk)

    def masked(s, layer, x, delta, b, c, a, d, live, _walk):
        old = lax.dynamic_index_in_dim(s, layer, axis=1, keepdims=False)
        new, y = _step_math(old, x.astype(f32), delta.astype(f32),
                            b.astype(f32), c.astype(f32), a.astype(f32),
                            d.astype(f32))
        new = jnp.where(live[:, None, None], new, old)
        return (lax.dynamic_update_index_in_dim(s, new, layer, axis=1),
                jnp.where(live[:, None], y, 0.0).astype(x.dtype))

    args = (s, layer, x, delta, b, c, a, d, live, walk)
    with jax.named_scope("selective_scan_step"):
        if not steps_in_kernel("tpu", s.shape, mesh):
            return masked(*args)
        return lax.platform_dependent(*args, tpu=kernel, default=masked)


# -- the convolution's tails -------------------------------------------------------------

def _tail_kernel(_layer_ref, t_in, a_ref, w_ref, bias_ref, live_ref, t_out,
                 c_ref, *, width):
    """Grid (B / LB,): program i is lanes [i LB, (i + 1) LB) of the layer.
    Refs: the tails [1, K - 1, LB, C], in and out one array; a, c [LB, C]
    (``a``: the first C columns of the array it lies in); w [K, C], bias [1,
    C]; live [LB, 1] int32. The window's product, the bias and the SiLU as
    ``ops.gated_delta.conv_step`` writes them."""
    taps, channels = w_ref.shape
    f32 = jnp.float32
    live = live_ref[...] != 0

    def chunk(k, _):
        cols = pl.ds(pl.multiple_of(k * width, width), width)
        window = [t_in[0, j, :, cols] for j in range(taps - 1)]
        window.append(a_ref[:, cols].astype(t_in.dtype))
        w = w_ref[:, cols].astype(f32)
        y = sum(window[j].astype(f32) * w[j:j + 1] for j in range(taps))
        y = y + bias_ref[:, cols].astype(f32)
        c_ref[:, cols] = jax.nn.silu(y).astype(c_ref.dtype)
        for j in range(taps - 1):
            t_out[0, j, :, cols] = jnp.where(live, window[j + 1], window[j])

    lax.fori_loop(0, channels // width, chunk, None)


@functools.partial(jax.jit, static_argnames=("interpret",))
def conv_tail_step_kernel(tails, layer, a, w, bias, live, walk=None,
                          interpret: bool = False):
    """The tails kernel itself, ``conv_tail_step``'s arguments and results.
    It is shown the array as [L, K - 1, B, C]: the order it lies in between
    executables (the compiler's choice for a parameter of [B, L, K - 1, C]
    in bfloat16: a layer's tap is then whole tiles of [lanes, C], as ``a``
    and ``c`` lie), so the two transpositions here move nothing. HBM bytes
    moved: the layer's tails read and written once, ``a`` in, ``c`` out."""
    lanes, _, rows, channels = tails.shape
    if not steps_in_kernel("tpu", tails.shape):
        raise ValueError(f"tails {tails.shape} do not fit the kernel")
    width = min(WIDTH, channels)
    block = next(n for n in TAIL_LANES if lanes % n == 0)
    walk = lanes_walked(live) if walk is None else walk
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    layer_taps = pl.BlockSpec((1, rows, block, channels),
                              lambda i, layer: (layer[0], 0, i, 0))
    mine = pl.BlockSpec((block, channels), lambda i, layer: (i, 0))
    t, c = pl.pallas_call(
        functools.partial(_tail_kernel, width=width),
        out_shape=(
            jax.ShapeDtypeStruct((tails.shape[1], rows, lanes, channels),
                                 tails.dtype),
            jax.ShapeDtypeStruct((lanes, channels), a.dtype)),
        # ``a``'s blocks are the first C columns of whatever width it has
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(lanes // block,),
            in_specs=[layer_taps, mine,
                      pl.BlockSpec(w.shape, lambda i, layer: (0, 0)),
                      pl.BlockSpec((1, channels), lambda i, layer: (0, 0)),
                      pl.BlockSpec((block, 1), lambda i, layer: (i, 0))],
            out_specs=(layer_taps, mine),
        ),
        # with the scalar counted: the tails are operand 1, result 0
        input_output_aliases={1: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        name="conv_tail_step",
        interpret=interpret,
    )(layer, tails.transpose(1, 2, 0, 3), a, w, bias.reshape(1, channels),
      walk.column)
    return c, t.transpose(2, 0, 1, 3)


@functools.partial(jax.jit, static_argnames=("mesh",))
def conv_tail_step(tails, layer, a, w, bias, live, mesh=None, walk=None):
    """One token a lane through the causal depthwise convolution of layer
    ``layer`` (int32, may be traced), over the tails of EVERY layer of the
    kind, [B, L, K - 1, C] (the cache's one array). a [B, C or more]: this
    token's input is its first C columns (the in-projection's [B, 2 C] goes
    in as it lies: the kernel's blocks stop at C, no slice is written), w
    [K, C], bias [C], live [B] bool; ``walk``: ``lanes_walked(live)`` where
    the caller has it. Returns ``(c [B, C] in a's dtype, tails)``:
    ``SiLU(window . w + bias)`` and the layer's tails shifted by ``a`` in
    the live lanes; an idle lane's and every other layer's tails as they
    were. On a TPU one Pallas kernel over the array in place; elsewhere,
    and as its oracle, ``ops.gated_delta.conv_step`` on the layer's slice."""
    from .gated_delta import conv_step

    layer = jnp.asarray(layer, jnp.int32)

    def kernel(tails, layer, a, w, bias, live, walk):
        return conv_tail_step_kernel(tails, layer, a, w, bias, live, walk)

    def masked(tails, layer, a, w, bias, live, _walk):
        tail = lax.dynamic_index_in_dim(tails, layer, 1, keepdims=False)
        c, tail = conv_step(a[:, :tails.shape[3]], tail, w, live, bias=bias)
        return c, lax.dynamic_update_index_in_dim(tails, tail, layer, 1)

    args = (tails, layer, a, w, bias, live, walk)
    with jax.named_scope("conv_tail_step"):
        if not steps_in_kernel("tpu", tails.shape, mesh):
            return masked(*args)
        return lax.platform_dependent(*args, tpu=kernel, default=masked)
