"""The gated delta rule's serving ops (``ops/gated_delta.py``) against the
recurrence itself: the chunked prefill at lengths that are no multiple of
the chunk and shorter than their bucket, the prefill kernel (interpreted)
against that scan, the convolution's tail, the decode kernel (interpreted)
with idle lanes, and the triangular inverse where the keys repeat. CPU
only; the kernels' compiles for a described v5e are in
``tests/test_burst_hlo.py``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from seldon_core_tpu.ops import gated_delta as gd


def _inputs(rng, b, t, h, d, dtype=jnp.float32):
    q = gd.l2norm(jnp.asarray(rng.normal(size=(b, t, h, d)))) * d ** -0.5
    k = gd.l2norm(jnp.asarray(rng.normal(size=(b, t, h, d))))
    v = jnp.asarray(rng.normal(size=(b, t, h, d)), jnp.float32)
    g = -jnp.asarray(rng.uniform(0.001, 2.0, size=(b, t, h)), jnp.float32)
    beta = jnp.asarray(rng.uniform(0.05, 0.95, size=(b, t, h)), jnp.float32)
    return q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta


def _recurrence(q, k, v, g, beta, n):
    """One sequence's first ``n`` tokens through the rule, one at a time:
    outputs [n, H, Dv] and the state after the last."""
    s = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), jnp.float32)
    out = []
    for t in range(n):
        s, o = gd._step_math(s, q[t], k[t], v[t], jnp.exp(g[t]), beta[t])
        out.append(o)
    return jnp.stack(out), s


@pytest.mark.parametrize("chunk", [16, 64])
def test_chunked_prefill_is_the_recurrence_up_to_each_sequences_length(chunk):
    rng = np.random.default_rng(0)
    t = 200                     # the bucket: no multiple of 64
    lens = np.array([200, 131, 64, 63, 65, 1, 17])
    q, k, v, g, beta = _inputs(rng, len(lens), t, 3, 32)
    with jax.default_matmul_precision("highest"):
        o, s = gd.gated_delta_prefill(q, k, v, g, beta, jnp.asarray(lens),
                                      chunk=chunk)
        for row, n in enumerate(lens):
            want_o, want_s = _recurrence(q[row], k[row], v[row], g[row],
                                         beta[row], int(n))
            np.testing.assert_allclose(o[row, :n], want_o, atol=2e-5)
            # the state stopped at the sequence's own last token: the
            # padding after it neither decayed nor wrote
            np.testing.assert_allclose(s[row], want_s, atol=2e-5)


def test_padding_changes_neither_state_nor_outputs():
    rng = np.random.default_rng(1)
    q, k, v, g, beta = _inputs(rng, 1, 96, 2, 32)
    lens = jnp.asarray([70])
    o, s = gd.gated_delta_prefill(q, k, v, g, beta, lens)
    # other tokens, decays and strengths past the length: the same answer
    q2, k2, v2, g2, beta2 = _inputs(np.random.default_rng(2), 1, 96, 2, 32)
    mix = lambda a, b: jnp.concatenate([a[:, :70], b[:, 70:]], axis=1)  # noqa: E731
    o2, s2 = gd.gated_delta_prefill(mix(q, q2), mix(k, k2), mix(v, v2),
                                    mix(g, g2), mix(beta, beta2), lens)
    assert jnp.array_equal(s, s2) and jnp.array_equal(o[:, :70], o2[:, :70])


def test_repeated_keys_do_not_blow_the_triangular_inverse_up():
    """Every key the same and every write at full strength: the powers of
    the chunk's matrix grow like binomials (C(63, 32) ~ 1e18), its inverse
    does not. The substitution forms no power."""
    c = 64
    a = jnp.tril(jnp.ones((c, c), jnp.float32), -1)
    inv = gd._unit_lower_inverse(a[None])[0]
    np.testing.assert_allclose(inv @ (jnp.eye(c) + a), jnp.eye(c), atol=1e-5)
    assert float(jnp.abs(inv).max()) <= 1.0 + 1e-6
    rng = np.random.default_rng(3)
    b = jnp.tril(jnp.asarray(rng.normal(size=(2, 5, c, c)), jnp.float32), -1) * 0.3
    got = gd._unit_lower_inverse(b)
    want = jnp.linalg.inv(jnp.eye(c) + b)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_convolution_prefill_and_step_agree_and_keep_the_tail():
    rng = np.random.default_rng(4)
    b, t, c, kw = 3, 40, 24, 4
    x = jnp.asarray(rng.normal(size=(b, t, c)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(kw, c)), jnp.float32)
    lens = jnp.asarray([40, 2, 17])
    y, tail = gd.conv_prefill(x, w, lens)
    # the plain causal convolution
    pad = jnp.concatenate([jnp.zeros((b, kw - 1, c)), x], axis=1)
    want = jax.nn.silu(sum(pad[:, j:j + t] * w[j] for j in range(kw)))
    np.testing.assert_allclose(y, want, atol=1e-6)
    # the tail is the last three inputs before each sequence's length,
    # zeros before its start, whatever lies in the padding
    for row, n in enumerate([40, 2, 17]):
        want_tail = pad[row, n:n + kw - 1]
        np.testing.assert_array_equal(tail[row], want_tail)
    # one more token through the step is the convolution at that position
    live = jnp.asarray([False, True, True])
    nxt = jnp.asarray(rng.normal(size=(b, c)), jnp.float32)
    y1, tail1 = gd.conv_step(nxt, tail, w, live)
    for row, n in ((1, 2), (2, 17)):
        full = jnp.concatenate([x[row, :n], nxt[row][None]])
        fpad = jnp.concatenate([jnp.zeros((kw - 1, c)), full])
        np.testing.assert_allclose(
            y1[row], jax.nn.silu(sum(fpad[n + j] * w[j] for j in range(kw))),
            atol=1e-6)
        np.testing.assert_array_equal(tail1[row], fpad[n + 1:n + kw])
    # an idle lane's tail stays as it is
    np.testing.assert_array_equal(tail1[0], tail[0])


@pytest.mark.parametrize("live", [[True, False, True, True, False, True],
                                  [False] * 6, [True] * 6])
def test_step_kernel_interpreted_is_the_rule_and_skips_idle_lanes(live):
    rng = np.random.default_rng(5)
    b, h, d = 6, 16, 128
    s = jnp.asarray(rng.normal(size=(b, h, d, d)), jnp.float32)
    q, k, v, g, beta = (a[:, 0] for a in _inputs(rng, b, 1, h, d))
    live = jnp.asarray(live)
    s1, o1 = gd.gated_delta_step_kernel(s, q, k, v, jnp.exp(g), beta, live,
                                        interpret=True)
    want_s, want_o = gd._step_math(s, q, k, v, jnp.exp(g), beta)
    for lane in range(b):
        if live[lane]:
            np.testing.assert_allclose(s1[lane], want_s[lane], atol=1e-5)
            np.testing.assert_allclose(o1[lane], want_o[lane], atol=1e-5)
        else:
            # neither read nor written: bit for bit what it was
            np.testing.assert_array_equal(s1[lane], s[lane])
            assert not o1[lane].any()


def test_step_off_a_tpu_is_the_same_rule_under_a_mask():
    rng = np.random.default_rng(6)
    b, h, d = 4, 4, 32
    s = jnp.asarray(rng.normal(size=(b, h, d, d)), jnp.float32)
    q, k, v, g, beta = (a[:, 0] for a in _inputs(rng, b, 1, h, d))
    live = jnp.asarray([True, False, True, False])
    s1, o1 = gd.gated_delta_step(s, q, k, v, g, beta, live)
    want_s, want_o = gd._step_math(s, q, k, v, jnp.exp(g), beta)
    np.testing.assert_array_equal(s1[1], s[1])
    np.testing.assert_allclose(s1[0], want_s[0], atol=1e-6)
    np.testing.assert_allclose(o1[2], want_o[2], atol=1e-6)
    assert not o1[3].any()
    # a prefill's state carried on by steps is the longer prefill's state
    q, k, v, g, beta = _inputs(rng, 1, 70, h, d)
    _, s66 = gd.gated_delta_prefill(q, k, v, g, beta, jnp.asarray([66]))
    _, s70 = gd.gated_delta_prefill(q, k, v, g, beta, jnp.asarray([70]))
    s = s66
    for t in range(66, 70):
        s, _ = gd.gated_delta_step(s, q[:, t], k[:, t], v[:, t], g[:, t],
                                   beta[:, t], jnp.asarray([True]))
    np.testing.assert_allclose(s, s70, atol=2e-5)


def test_the_kernel_is_chosen_by_what_the_lowering_can_see():
    assert gd.steps_in_kernel("tpu", (64, 32, 128, 128))
    assert not gd.steps_in_kernel("cpu", (64, 32, 128, 128))
    assert not gd.steps_in_kernel("tpu", (64, 32, 128, 128), mesh=object())
    assert not gd.steps_in_kernel("tpu", (4, 4, 32, 32))
    # the prefill's: the same rule over q's and v's shapes
    q = (1, 4096, 32, 128)
    assert gd.prefills_in_kernel("tpu", q, q)
    assert gd.prefills_in_kernel("tpu", (8, 100, 2, 128), (8, 100, 2, 128), 128)
    assert not gd.prefills_in_kernel("cpu", q, q)
    assert not gd.prefills_in_kernel("tpu", q, q, mesh=object())
    assert not gd.prefills_in_kernel("tpu", (1, 4096, 32, 32), (1, 4096, 32, 32))
    assert not gd.prefills_in_kernel("tpu", q, (1, 4096, 32, 256))   # not square
    assert not gd.prefills_in_kernel("tpu", (1, 4096, 12, 128), (1, 4096, 12, 128))
    assert not gd.prefills_in_kernel("tpu", q, q, chunk=16)
    assert not gd.prefills_in_kernel("tpu", q, q, chunk=192)


# -- the prefill kernel, interpreted -----------------------------------------------------

def _against_the_scan(q, k, v, g, beta, lens, chunk=gd.CHUNK):
    """The kernel (interpreted) and ``_chunk`` under ``lax.scan`` on the
    same arguments: outputs at each sequence's real positions, the final
    state, and zeros in every chunk past the last one walked."""
    lens = jnp.asarray(lens, jnp.int32)
    o, s = gd.gated_delta_prefill_kernel(q, k, v, g, beta, lens, chunk=chunk,
                                         interpret=True)
    want_o, want_s = gd._prefill_scanned(q, k, v, g, beta, lens, chunk=chunk)
    assert o.shape == want_o.shape and o.dtype == want_o.dtype
    assert s.shape == want_s.shape and s.dtype == jnp.float32
    f32 = jnp.float32
    # a bfloat16 output may round the other way: one step of its 8 bits
    ulp = 2.0 ** -7 if o.dtype == jnp.bfloat16 else 0.0
    for row, n in enumerate(np.asarray(lens)):
        np.testing.assert_allclose(o[row, :n].astype(f32),
                                   want_o[row, :n].astype(f32), atol=2e-5,
                                   rtol=ulp)
        np.testing.assert_allclose(s[row], want_s[row], atol=2e-5)
        assert not np.asarray(o[row, -(-n // chunk) * chunk:]).any()
        if n == 0:
            assert not np.asarray(s[row]).any()
    return o, s


@pytest.mark.parametrize("t,n", [
    (256, 1), (256, 63), (256, 64), (256, 65), (4096, 2048), (4096, 4096),
    (256, 0)], ids=["1", "63", "64", "65", "2048_of_4096", "the_whole_bucket",
                    "0"])
def test_prefill_kernel_interpreted_is_the_scan_up_to_the_length(t, n):
    q, k, v, g, beta = _inputs(np.random.default_rng(7), 1, t, 2, 128)
    _against_the_scan(q, k, v, g, beta, [n])


def test_prefill_kernel_takes_four_lengths_in_one_call():
    """Rows of one call stop at their own lengths: head blocks of 8 (two
    of them), bfloat16 operands as served."""
    q, k, v, g, beta = _inputs(np.random.default_rng(8), 4, 320, 16, 128,
                               jnp.bfloat16)
    o, _ = _against_the_scan(q, k, v, g, beta, [320, 129, 64, 7])
    assert o.dtype == jnp.bfloat16


def test_prefill_kernel_pads_a_short_bucket_to_one_chunk():
    q, k, v, g, beta = _inputs(np.random.default_rng(9), 2, 32, 2, 128)
    o, _ = _against_the_scan(q, k, v, g, beta, [32, 5])
    assert o.shape == (2, 32, 2, 128)
    # and chunks of 128: three levels of halves to put together
    q, k, v, g, beta = _inputs(np.random.default_rng(9), 2, 200, 2, 128)
    _against_the_scan(q, k, v, g, beta, [200, 129], chunk=128)


def test_prefill_kernel_writes_zeros_where_the_scan_left_values_of_no_use():
    """Past a sequence's last chunk nothing is fetched or computed, and
    what the next layer reads there is defined: zeros, whatever lies in
    the padding (here: NaN)."""
    q, k, v, g, beta = _inputs(np.random.default_rng(10), 2, 256, 2, 128)
    past = jnp.arange(256)[None, :, None] >= 128
    nan = lambda a: jnp.where(past[..., None] if a.ndim == 4 else past,  # noqa: E731
                              jnp.nan, a)
    o, s = gd.gated_delta_prefill_kernel(
        nan(q), nan(k), nan(v), nan(g), nan(beta), jnp.asarray([100, 128]),
        interpret=True)
    want_o, want_s = gd._prefill_scanned(q, k, v, g, beta,
                                         jnp.asarray([100, 128]))
    assert not np.asarray(o[:, 128:]).any()
    np.testing.assert_allclose(s, want_s, atol=2e-5)
    np.testing.assert_allclose(o[0, :100], want_o[0, :100], atol=2e-5)
    np.testing.assert_allclose(o[1, :128], want_o[1, :128], atol=2e-5)


def test_a_kernel_prefills_state_carried_on_by_steps_is_the_longer_prefills():
    q, k, v, g, beta = _inputs(np.random.default_rng(11), 1, 70, 2, 128)
    run = lambda n: gd.gated_delta_prefill_kernel(  # noqa: E731
        q, k, v, g, beta, jnp.asarray([n]), interpret=True)[1]
    s = run(66)
    for t in range(66, 70):
        s, _ = gd.gated_delta_step(s, q[:, t], k[:, t], v[:, t], g[:, t],
                                   beta[:, t], jnp.asarray([True]))
    np.testing.assert_allclose(s, run(70), atol=2e-5)


def test_off_a_tpu_the_prefill_is_the_scan_bit_for_bit():
    """Heads the kernel takes and heads it does not: lowered for a CPU,
    ``gated_delta_prefill`` is ``_chunk`` under ``lax.scan`` either way."""
    for d in (128, 32):
        q, k, v, g, beta = _inputs(np.random.default_rng(12), 2, 96, 2, d)
        lens = jnp.asarray([96, 40])
        o, s = gd.gated_delta_prefill(q, k, v, g, beta, lens)
        want_o, want_s = gd._prefill_scanned(q, k, v, g, beta, lens)
        np.testing.assert_array_equal(o, want_o)
        np.testing.assert_array_equal(s, want_s)
    with pytest.raises(ValueError, match="do not fit the kernel"):
        gd.gated_delta_prefill_kernel(q, k, v, g, beta, lens, interpret=True)
