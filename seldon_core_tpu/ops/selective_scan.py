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
                      a Pallas kernel whose grid walks the LIVE lanes
                      (scalars prefetched); each program copies one lane's
                      state of layer ``layer`` in, updates it in VMEM and
                      copies it out, once. The state is aliased in and
                      out: an idle lane's, and every other layer's, is
                      neither read nor written. Elsewhere the same
                      arithmetic in ``jax.numpy`` under a mask

Operands (``x``, ``delta``, ``b``, ``c``) are the served dtype; the state,
the decay and the recurrence are float32.
"""

from __future__ import annotations

import functools

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


def _step_kernel(order_ref, n_ref, _layer_ref, s_in, x_ref, dt_ref, b_ref,
                 c_ref, a_ref, d_ref, s_out, y_ref, *, width):
    """Grid (B,): program i updates the i-th live lane's state of the
    layer. Past the live lanes the index maps stay on the last block
    fetched and nothing is computed. Refs: s [1, 1, N, C]; x, delta, y [1,
    1, C]; b, c [1, N, 128] (``_wide``); a [N, C], d [1, C]; ``order_ref``
    SMEM [B] the live lanes first, ``n_ref`` [1], ``_layer_ref`` [1] (the
    index maps')."""
    i = pl.program_id(0)
    channels = x_ref.shape[2]

    @pl.when(i < n_ref[0])
    def _():
        for at in range(0, channels, width):
            cols = slice(at, at + width)
            x, dt = x_ref[0, :, cols], dt_ref[0, :, cols]          # [1, width]
            s = jnp.exp(dt * a_ref[:, cols]) * s_in[0, 0, :, cols] + (
                _tiled(b_ref[0], width) * (dt * x))
            s_out[0, 0, :, cols] = s
            y_ref[0, :, cols] = jnp.sum(
                s * _tiled(c_ref[0], width), axis=0, keepdims=True) + (
                    d_ref[:, cols] * x)

    # no live lane at all: the one block every program maps to goes back as
    # it came
    @pl.when(n_ref[0] == 0)
    def _():
        s_out[...] = s_in[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def selective_scan_step_kernel(s, layer, x, delta, b, c, a, d, live,
                               interpret: bool = False):
    """The decode kernel itself: s [B, L, N, C] float32 (aliased in and
    out), ``layer`` the one of the L that steps, x, delta [B, C], b, c [B,
    N], a [N, C], d [C], live [B] bool -> ``(s, y [B, C] float32)``; an
    idle lane's ``y`` is zeros and its state is not touched, nor is any
    other layer's. HBM bytes moved: live lanes x N x C x 4 B, read once and
    written once."""
    bsz, _, n_state, channels = s.shape
    if not steps_in_kernel("tpu", s.shape):
        raise ValueError(f"state {s.shape} does not fit the kernel")
    width = min(WIDTH, channels)
    f32 = jnp.float32
    order = jnp.argsort(~live, stable=True).astype(jnp.int32)
    n = live.sum(dtype=jnp.int32).reshape(1)
    layer = jnp.asarray(layer, jnp.int32).reshape(1)

    def lane(i, order, n):
        return order[jnp.minimum(i, jnp.maximum(n[0] - 1, 0))]

    row = pl.BlockSpec((1, 1, channels),
                       lambda i, order, n, layer: (lane(i, order, n), 0, 0))
    col = pl.BlockSpec((1, n_state, LANES),
                       lambda i, order, n, layer: (lane(i, order, n), 0, 0))
    state = pl.BlockSpec(
        (1, 1, n_state, channels),
        lambda i, order, n, layer: (lane(i, order, n), layer[0], 0, 0))
    s, y = pl.pallas_call(
        functools.partial(_step_kernel, width=width),
        out_shape=(jax.ShapeDtypeStruct(s.shape, f32),
                   jax.ShapeDtypeStruct((bsz, 1, channels), f32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(bsz,),
            in_specs=[state, row, row, col, col,
                      pl.BlockSpec((n_state, channels),
                                   lambda i, order, n, layer: (0, 0)),
                      pl.BlockSpec((1, channels),
                                   lambda i, order, n, layer: (0, 0))],
            out_specs=(state, row),
        ),
        # with the three scalars counted: the state is operand 3, result 0
        input_output_aliases={3: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="selective_scan_step",
        interpret=interpret,
    )(order, n, layer, s, x.astype(f32)[:, None], delta.astype(f32)[:, None],
      _wide(b), _wide(c), a.astype(f32), d.astype(f32).reshape(1, channels))
    return s, jnp.where(live[:, None], y[:, 0], 0.0)


def steps_in_kernel(platform, s_shape, mesh=None) -> bool:
    """Whether ``selective_scan_step()``, lowered for ``platform``, is the
    kernel: ``prefills_in_kernel``'s rule on the state's shape [B, L, N,
    C]."""
    return prefills_in_kernel(platform, (s_shape[0], 1, s_shape[3]),
                              s_shape[2:], mesh)


@functools.partial(jax.jit, static_argnames=("mesh",))
def selective_scan_step(s, layer, x, delta, b, c, a, d, live, mesh=None):
    """One token a lane in layer ``layer`` (int32, may be traced) of the
    state s [B, L, N, C] float32. x, delta [B, C], b, c [B, N] in the served
    dtype; a [N, C], d [C]; live [B] bool. Returns ``(s, y [B, C]
    float32)``: a live lane's state of that layer after its token and the
    token's output (an idle lane's: zeros); every other lane's and layer's
    state as it was."""
    f32 = jnp.float32
    layer = jnp.asarray(layer, jnp.int32)

    def kernel(s, layer, x, delta, b, c, a, d, live):
        return selective_scan_step_kernel(s, layer, x, delta, b, c, a, d, live)

    def masked(s, layer, x, delta, b, c, a, d, live):
        old = lax.dynamic_index_in_dim(s, layer, axis=1, keepdims=False)
        new, y = _step_math(old, x.astype(f32), delta.astype(f32),
                            b.astype(f32), c.astype(f32), a.astype(f32),
                            d.astype(f32))
        new = jnp.where(live[:, None, None], new, old)
        return (lax.dynamic_update_index_in_dim(s, new, layer, axis=1),
                jnp.where(live[:, None], y, 0.0))

    args = (s, layer, x, delta, b, c, a, d, live)
    with jax.named_scope("selective_scan_step"):
        if not steps_in_kernel("tpu", s.shape, mesh):
            return masked(*args)
        return lax.platform_dependent(*args, tpu=kernel, default=masked)
