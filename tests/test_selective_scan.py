"""The Mamba-1 selective scan (``ops/selective_scan.py``) on the CPU: the
plain forms against the recurrence as it is written, the three Pallas
kernels in interpret mode against the plain forms (lengths inside a bucket;
idle lanes alone, beside live ones and by the group, a layer among layers, a
float32 state under bfloat16 operands, the tails shifted in place), the
rule that says which lowering takes the kernels, and the convolution's new
``bias`` argument, which as a Python ``None`` leaves the other two callers'
jaxprs character for character what they were."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from seldon_core_tpu.ops import gated_delta
from seldon_core_tpu.ops import selective_scan as ss

N = 16


def draw(B, T, C, dtype=jnp.bfloat16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (B, T, C)).astype(dtype)
    delta = jax.nn.softplus(jax.random.normal(ks[1], (B, T, C)) - 3).astype(dtype)
    b = jax.random.normal(ks[2], (B, T, N)).astype(dtype)
    c = jax.random.normal(ks[3], (B, T, N)).astype(dtype)
    a = -jnp.exp(jax.random.uniform(ks[4], (N, C), minval=0.0, maxval=2.7))
    d = jax.random.normal(ks[5], (C,))
    return x, delta, b, c, a, d


def written(x, delta, b, c, a, d, length):
    """The recurrence as the issue writes it, one sequence, float64 numpy:
    the state is [C, N] here, the published layout."""
    x, delta, b, c = (np.asarray(v, np.float64) for v in (x, delta, b, c))
    a, d = np.asarray(a, np.float64).T, np.asarray(d, np.float64)   # [C, N]
    s = np.zeros_like(a)
    ys = []
    for t in range(length):
        s = np.exp(delta[t][:, None] * a) * s + (
            (delta[t] * x[t])[:, None] * b[t][None, :])
        ys.append(s @ c[t] + d * x[t])
    return np.stack(ys), s.T


@pytest.mark.parametrize("lens", [(24, 7, 1), (24, 24, 24)])
def test_the_plain_prefill_is_the_recurrence_as_written(lens):
    x, delta, b, c, a, d = draw(3, 24, 128, jnp.float32)
    y, s = ss.selective_scan_prefill(x, delta, b, c, a, d, jnp.asarray(lens))
    assert s.dtype == jnp.float32 and s.shape == (3, N, 128)
    for i, n in enumerate(lens):
        want_y, want_s = written(x[i], delta[i], b[i], c[i], a, d, n)
        np.testing.assert_allclose(np.asarray(y[i, :n]), want_y, atol=2e-5)
        # the state stops at the sequence's own last token, whatever the
        # bucket holds after it
        np.testing.assert_allclose(np.asarray(s[i]), want_s, atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("lens", [(160, 37, 70, 1), (64, 128, 129, 16)])
def test_the_prefill_kernel_is_its_oracle_at_lengths_inside_a_bucket(dtype, lens):
    """Interpreted: chunks of 64, blocks of 16 steps, channel groups of
    512; lengths on a chunk's edge, on a block's, inside one, and one
    token."""
    x, delta, b, c, a, d = draw(4, 160, 1024, dtype, seed=3)
    lens = jnp.asarray(lens, jnp.int32)
    y0, s0 = ss._prefill_scanned(x, delta, b, c, a, d, lens)
    y1, s1 = ss.selective_scan_prefill_kernel(x, delta, b, c, a, d, lens,
                                              interpret=True)
    assert s1.dtype == jnp.float32 and y1.dtype == dtype
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s0), atol=1e-5)
    y0, y1 = (np.asarray(v, np.float32) for v in (y0, y1))
    for i, n in enumerate(lens.tolist()):
        np.testing.assert_allclose(y1[i, :n], y0[i, :n], atol=0.02, rtol=0.01)
        # past the length: zeros, or the values of a step that changed
        # nothing (within the sequence's last block of 16), never garbage
        assert np.isfinite(y1[i]).all()
        assert not y1[i, -(-n // ss.ROWS) * ss.ROWS:].any()


def test_the_prefill_kernel_pads_a_bucket_that_is_no_multiple_of_its_chunk():
    x, delta, b, c, a, d = draw(2, 40, 512, jnp.float32, seed=5)
    lens = jnp.asarray([40, 9], jnp.int32)
    y0, s0 = ss._prefill_scanned(x, delta, b, c, a, d, lens)
    y1, s1 = ss.selective_scan_prefill_kernel(x, delta, b, c, a, d, lens,
                                              interpret=True)
    assert y1.shape == y0.shape
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s0), atol=1e-5)
    np.testing.assert_allclose(np.asarray(y1[0]), np.asarray(y0[0]), atol=1e-4)


LANES, LAYERS, TAPS = 32, 3, 4
# which lanes step: the kernels walk them in groups of 8 (the state) and
# blocks of 16 or 32 (the tails)
LIVE = {
    "all": np.ones(LANES, bool),
    "none": np.zeros(LANES, bool),
    "one": np.arange(LANES) == 13,
    "every_eighth_idle": np.arange(LANES) % 8 != 7,
    "a_group_of_8_idle": np.arange(LANES) // 8 != 2,
    "the_first_groups_idle": np.arange(LANES) >= 17,
    "mixed": np.tile([True, False, True, True, False, True, False, False], 4),
}


@pytest.fixture(scope="module")
def stepped():
    C = 1024
    x, delta, b, c, a, d = draw(1, LANES, C, seed=7)
    ks = jax.random.split(jax.random.PRNGKey(9), 5)
    state = jax.random.normal(ks[0], (LANES, LAYERS, N, C))
    tails = jax.random.normal(ks[1], (LANES, LAYERS, TAPS - 1, C)).astype(
        jnp.bfloat16)
    # the in-projection's product: the convolution's input is its first half
    az = jax.random.normal(ks[2], (LANES, 2 * C)).astype(jnp.bfloat16)
    w = (jax.random.normal(ks[3], (TAPS, C)) / 2).astype(jnp.bfloat16)
    bias = jax.random.uniform(ks[4], (C,), minval=-0.5, maxval=0.5).astype(
        jnp.bfloat16)
    return state, (x[0], delta[0], b[0], c[0], a, d), tails, (az, w, bias)


def close(got, want):
    """The file's limits for an output in bfloat16."""
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=0.02,
                               rtol=0.01)


def only_live_lanes_of_the_layer_moved(got, was, layer, live):
    idle = ~np.asarray(live)
    others = [l for l in range(was.shape[1]) if l != layer]
    assert np.array_equal(np.asarray(got)[idle], np.asarray(was)[idle])
    assert np.array_equal(np.asarray(got)[:, others], np.asarray(was)[:, others])


@pytest.mark.parametrize("layer", range(LAYERS))
@pytest.mark.parametrize("live", LIVE)
def test_the_step_touches_live_lanes_of_one_layer_alone(stepped, layer, live):
    """The plain form against the recurrence, the interpreted kernel (fed x
    and delta in bfloat16 as they lie, b and c as they come) against the
    plain form: an idle lane's state and every other layer's are bit for bit
    what they were, under both, a whole group of idle lanes and an idle lane
    beside live ones alike; an idle lane's output is zeros."""
    state, (x, delta, b, c, a, d), _tails, _conv = stepped
    live = jnp.asarray(LIVE[live])
    new, y = ss.selective_scan_step(state, jnp.int32(layer), x, delta, b, c, a,
                                    d, live)
    assert new.dtype == jnp.float32 and y.dtype == x.dtype == jnp.bfloat16
    f64 = lambda v: np.asarray(v, np.float64)  # noqa: E731
    for j in np.flatnonzero(np.asarray(live)):
        s = np.exp(f64(delta[j])[None, :] * f64(a)) * f64(state[j, layer]) + (
            f64(b[j])[:, None] * (f64(delta[j]) * f64(x[j]))[None, :])
        np.testing.assert_allclose(np.asarray(new[j, layer]), s, atol=1e-5)
        close(y[j], f64(c[j]) @ s + f64(d) * f64(x[j]))
    kernel, y_k = ss.selective_scan_step_kernel(
        jnp.copy(state), jnp.int32(layer), x, delta, b, c, a, d, live,
        interpret=True)
    assert kernel.dtype == jnp.float32 and y_k.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(kernel), np.asarray(new), atol=1e-6)
    close(y_k, y)
    idle = ~np.asarray(live)
    for got in (new, kernel):
        only_live_lanes_of_the_layer_moved(got, state, layer, live)
    assert not np.asarray(y, np.float32)[idle].any()
    assert not np.asarray(y_k, np.float32)[idle].any()
    # what a caller computes once a step and hands every layer
    walked, y_w = ss.selective_scan_step_kernel(
        jnp.copy(state), jnp.int32(layer), x, delta, b, c, a, d, live,
        ss.lanes_walked(live), interpret=True)
    assert np.array_equal(np.asarray(walked), np.asarray(kernel))
    assert np.array_equal(np.asarray(y_w), np.asarray(y_k))


@pytest.mark.parametrize("layer", range(LAYERS))
@pytest.mark.parametrize("live", LIVE)
def test_the_tails_step_is_the_convolutions_on_the_layers_slice(stepped, layer,
                                                              live):
    """The plain form IS ``conv_step`` on the layer's slice; the interpreted
    kernel, which reads the array as [layers, taps, lanes, C] and ``a`` where
    it lies in the in-projection's product, against it: ``c`` and the live
    lanes' shifted tails; an idle lane's tails and every other layer's bit
    for bit what they were."""
    _state, _scan, tails, (az, w, bias) = stepped
    live = jnp.asarray(LIVE[live])
    C = tails.shape[3]
    want_c, want = gated_delta.conv_step(az[:, :C], tails[:, layer], w, live,
                                         bias=bias)
    for a in (az, az[:, :C]):
        c, new = ss.conv_tail_step(tails, jnp.int32(layer), a, w, bias, live)
        assert c.dtype == jnp.bfloat16 and new.dtype == tails.dtype
        assert np.array_equal(np.asarray(c), np.asarray(want_c))
        assert np.array_equal(np.asarray(new[:, layer]), np.asarray(want))
        c_k, kernel = ss.conv_tail_step_kernel(
            jnp.copy(tails), jnp.int32(layer), a, w, bias, live, interpret=True)
        assert c_k.dtype == jnp.bfloat16 and kernel.shape == tails.shape
        close(c_k, c)
        # the tails are copies: to the bit
        assert np.array_equal(np.asarray(kernel), np.asarray(new))
        for got in (new, kernel):
            only_live_lanes_of_the_layer_moved(got, tails, layer, live)
    # a live lane's new tail ends with this token's input
    on = np.asarray(live)
    assert np.array_equal(np.asarray(kernel)[on, layer, -1],
                          np.asarray(az[:, :C])[on])


def test_a_state_that_is_no_whole_sublane_tile_steps_in_the_kernel():
    """N = 12: the prefill's kernel refuses it, the step's takes N as a whole
    axis of its blocks."""
    lanes, C, n = 16, 256, 12
    ks = jax.random.split(jax.random.PRNGKey(4), 6)
    state = jax.random.normal(ks[0], (lanes, 2, n, C))
    x, delta = (jax.random.normal(k, (lanes, C)).astype(jnp.bfloat16)
                for k in ks[1:3])
    b, c = (jax.random.normal(k, (lanes, n)).astype(jnp.bfloat16)
            for k in ks[3:5])
    a, d = -jnp.exp(jax.random.uniform(ks[5], (n, C))), jnp.ones((C,))
    live = jnp.arange(lanes) % 3 != 0
    delta = jax.nn.softplus(delta.astype(jnp.float32) - 3).astype(jnp.bfloat16)
    new, y = ss.selective_scan_step(state, jnp.int32(1), x, delta, b, c, a, d,
                                    live)
    kernel, y_k = ss.selective_scan_step_kernel(
        jnp.copy(state), jnp.int32(1), x, delta, b, c, a, d, live,
        interpret=True)
    np.testing.assert_allclose(np.asarray(kernel), np.asarray(new), atol=1e-6)
    close(y_k, y)
    only_live_lanes_of_the_layer_moved(kernel, state, 1, live)


def test_the_lanes_are_walked_in_groups_that_hold_a_live_lane():
    """``lanes_walked``: a group without a live lane stays on the block the
    program before it held (the first live group's, before any)."""
    for name, groups in (
            ("all", [0, 1, 2, 3]), ("none", [0, 0, 0, 0]),
            ("one", [1, 1, 1, 1]), ("a_group_of_8_idle", [0, 1, 1, 3]),
            ("the_first_groups_idle", [2, 2, 2, 3])):
        held, flags, column = ss.lanes_walked(jnp.asarray(LIVE[name]))
        assert held.tolist() == groups
        assert flags.dtype == jnp.int32 and column.shape == (LANES, 1)
        assert np.array_equal(np.asarray(flags, bool), LIVE[name])
        assert np.array_equal(np.asarray(column[:, 0]), np.asarray(flags))
    # lanes that are no whole groups: nothing to hand a kernel
    assert ss.lanes_walked(jnp.ones((6,), bool)) is None


def test_a_bfloat16_state_is_another_answer():
    """The state is float32 whatever the operands are: rounded to bfloat16
    after every token (what a cache in the model's dtype would hold) it is
    off by a hundred times the float32 recurrence's own error."""
    x, delta, b, c, a, d = draw(1, 200, 128, jnp.bfloat16, seed=11)
    lens = jnp.asarray([200], jnp.int32)
    _, s = ss.selective_scan_prefill(x, delta, b, c, a, d, lens)
    _, want = written(x[0], delta[0], b[0], c[0], a, d, 200)
    rounded = np.zeros_like(want, np.float32)
    for t in range(200):
        one = ss._step_math(
            jnp.asarray(rounded)[None], *(v[:, t].astype(jnp.float32)
                                          for v in (x, delta, b, c)), a, d)[0]
        rounded = np.asarray(one[0].astype(jnp.bfloat16).astype(jnp.float32))
    scale = np.linalg.norm(want)
    assert np.linalg.norm(np.asarray(s[0]) - want) / scale < 1e-5
    assert np.linalg.norm(rounded - want) / scale > 1e-3


def test_the_rule_that_takes_the_kernels_is_on_the_calls_shapes_alone():
    assert ss.prefills_in_kernel("tpu", (8, 1024, 5120), (16, 5120))
    assert ss.prefills_in_kernel("tpu", (1, 64, 256), (8, 256))
    # the step's two kernels, one rule: the state's shape and the tails'
    assert ss.steps_in_kernel("tpu", (192, 26, 16, 5120))
    assert ss.steps_in_kernel("tpu", (192, 26, 3, 5120))
    assert ss.steps_in_kernel("tpu", (16, 2, 2, 128))
    # not a TPU; a serving mesh; channels that are no whole group of lanes;
    # the prefill's state that is no whole sublane tile (the step's N is a
    # whole axis of its blocks); the step's lanes that are no whole tiles of
    # 16
    assert not ss.prefills_in_kernel("cpu", (8, 1024, 5120), (16, 5120))
    assert not ss.prefills_in_kernel("tpu", (8, 1024, 5120), (12, 5120))
    assert not ss.prefills_in_kernel("tpu", (1, 64, 5120 + 128), (16, 5248))
    for rows in (16, 12, 3):
        assert ss.steps_in_kernel("tpu", (192, 26, rows, 5120))
        assert not ss.steps_in_kernel("cpu", (192, 26, rows, 5120))
        assert not ss.steps_in_kernel("tpu", (192, 26, rows, 5120),
                                      mesh=object())
        assert not ss.steps_in_kernel("tpu", (16, 2, rows, 192))
        assert not ss.steps_in_kernel("tpu", (6, 3, rows, 1024))
        assert not ss.steps_in_kernel("tpu", (200, 26, rows, 5120))
    with pytest.raises(ValueError, match="do not fit the kernel"):
        ss.selective_scan_prefill_kernel(*draw(1, 16, 192), jnp.asarray([16]),
                                         interpret=True)
    live = jnp.ones((6,), bool)
    x, delta, b, c, a, d = draw(1, 6, 256, seed=1)
    with pytest.raises(ValueError, match="does not fit the kernel"):
        ss.selective_scan_step_kernel(
            jnp.zeros((6, 2, N, 256)), jnp.int32(0), x[0], delta[0], b[0], c[0],
            a, d, live, interpret=True)
    with pytest.raises(ValueError, match="do not fit the kernel"):
        ss.conv_tail_step_kernel(
            jnp.zeros((6, 2, 3, 256), jnp.bfloat16), jnp.int32(0), x[0],
            jnp.ones((4, 256), jnp.bfloat16), jnp.ones((256,), jnp.bfloat16),
            live, interpret=True)
    # off the rule the public entries are the plain forms, whatever lowers
    x, delta, b, c, a, d = draw(2, 8, 192, jnp.float32)
    text = jax.jit(ss.selective_scan_prefill).lower(
        x, delta, b, c, a, d, jnp.asarray([8, 3])).as_text()
    assert "tpu_custom_call" not in text
    x, delta, b, c, a, d = draw(1, 6, 256, seed=1)
    for text in (
            jax.jit(ss.selective_scan_step).lower(
                jnp.zeros((6, 2, N, 256)), jnp.int32(0), x[0], delta[0], b[0],
                c[0], a, d, live).as_text(),
            jax.jit(ss.conv_tail_step).lower(
                jnp.zeros((6, 2, 3, 256), jnp.bfloat16), jnp.int32(0), x[0],
                jnp.ones((4, 256), jnp.bfloat16),
                jnp.ones((256,), jnp.bfloat16), live).as_text()):
        assert "tpu_custom_call" not in text


# -- the convolution's bias: a Python None in the other callers' traces --------

def _conv_prefill_pr54(x, w, lens, activation="silu"):
    """``conv_prefill`` as PR 54 had it, character for character."""
    t = x.shape[1]
    k = w.shape[0]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    y = sum(padded[:, j:j + t].astype(jnp.float32)
            * w[j].astype(jnp.float32) for j in range(k))
    at = (lens.astype(jnp.int32)[:, None] - (k - 1)
          + jnp.arange(k - 1, dtype=jnp.int32))
    tail = jnp.take_along_axis(x, jnp.maximum(at, 0)[:, :, None], axis=1)
    tail = jnp.where(at[:, :, None] >= 0, tail, jnp.zeros_like(tail))
    return gated_delta._activated(y, activation).astype(x.dtype), tail


def _conv_step_pr54(x, tail, w, live, activation="silu"):
    """``conv_step`` as PR 54 had it."""
    window = jnp.concatenate([tail, x[:, None].astype(tail.dtype)], axis=1)
    y = jnp.sum(window.astype(jnp.float32) * w.astype(jnp.float32)[None], 1)
    new = jnp.where(live[:, None, None], window[:, 1:], tail)
    return gated_delta._activated(y, activation).astype(x.dtype), new


@pytest.mark.parametrize("activation,taps", [("silu", 4), (None, 3)])
def test_without_a_bias_the_convolution_traces_as_it_did(activation, taps):
    """qwen3-next's call (SiLU, 4 taps) and lfm2's (no activation, 3)."""
    x = jnp.ones((2, 9, 32), jnp.bfloat16)
    w = jnp.ones((taps, 32), jnp.bfloat16)
    lens = jnp.asarray([9, 4], jnp.int32)
    tail = jnp.ones((2, taps - 1, 32), jnp.bfloat16)
    live = jnp.asarray([True, False])
    assert str(jax.make_jaxpr(
        lambda *a: gated_delta.conv_prefill(*a, activation=activation))(
            x, w, lens)) == str(jax.make_jaxpr(
                lambda *a: _conv_prefill_pr54(*a, activation=activation))(
                    x, w, lens))
    assert str(jax.make_jaxpr(
        lambda *a: gated_delta.conv_step(*a, activation=activation))(
            x[:, 0], tail, w, live)) == str(jax.make_jaxpr(
                lambda *a: _conv_step_pr54(*a, activation=activation))(
                    x[:, 0], tail, w, live))


def test_the_bias_goes_in_before_the_activation_in_prefill_and_step_alike():
    key = jax.random.PRNGKey(2)
    x = jax.random.normal(key, (2, 10, 16))
    w = jax.random.normal(jax.random.PRNGKey(3), (4, 16))
    bias = jax.random.normal(jax.random.PRNGKey(4), (16,))
    lens = jnp.asarray([10, 6], jnp.int32)
    y, tail = gated_delta.conv_prefill(x, w, lens, bias=bias)
    plain, same_tail = gated_delta.conv_prefill(x, w, lens, activation=None)
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(jax.nn.silu(plain + bias)), atol=1e-6)
    assert np.array_equal(np.asarray(tail), np.asarray(same_tail))
    # the step from the prefill's tail is the prefill's next position
    longer, _ = gated_delta.conv_prefill(x, w, jnp.asarray([10, 7]), bias=bias)
    y_step, new = gated_delta.conv_step(
        x[:, 6], tail, w, jnp.asarray([False, True]), bias=bias)
    np.testing.assert_allclose(np.asarray(y_step[1]), np.asarray(longer[1, 6]),
                               atol=1e-5)
    assert np.array_equal(np.asarray(new[0]), np.asarray(tail[0]))
