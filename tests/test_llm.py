"""DecoderLM tests: causality, decode==forward, generate, loss."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from seldon_core_tpu.models.llm import DecoderLM


@pytest.fixture(scope="module")
def small_model():
    m = DecoderLM(
        vocab_size=128, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=128, max_seq=64, dtype="float32",
    )
    return m, m.init_params(0)


TOKS = jnp.asarray(np.random.RandomState(0).randint(0, 128, (2, 10)), jnp.int32)


def test_forward_shape_and_causality(small_model):
    m, p = small_model
    logits = jax.jit(m.apply)(p, TOKS)
    assert logits.shape == (2, 10, 128)
    toks2 = TOKS.at[:, 7].set((TOKS[:, 7] + 1) % 128)
    logits2 = jax.jit(m.apply)(p, toks2)
    np.testing.assert_allclose(logits[:, :7], logits2[:, :7], atol=1e-5)
    assert not np.allclose(logits[:, 7:], logits2[:, 7:], atol=1e-5)


def test_kv_cache_decode_matches_forward(small_model):
    m, p = small_model
    logits = jax.jit(m.apply)(p, TOKS)
    cache = m.init_cache(2, 10)
    step = jax.jit(m.decode_step)
    outs = []
    for t in range(10):
        lg, cache = step(p, cache, TOKS[:, t : t + 1], t)
        outs.append(lg)
    dec = jnp.stack(outs, 1)
    np.testing.assert_allclose(dec, logits, atol=2e-3)


def test_generate_greedy_deterministic(small_model):
    m, p = small_model
    gen_fn = jax.jit(lambda p, x: m.generate(p, x, 5))
    g1 = gen_fn(p, TOKS[:, :4])
    g2 = gen_fn(p, TOKS[:, :4])
    assert g1.shape == (2, 9)
    np.testing.assert_array_equal(g1, g2)
    np.testing.assert_array_equal(g1[:, :4], TOKS[:, :4])


def test_gqa_head_counts():
    m = DecoderLM(vocab_size=32, d_model=32, n_layers=1, n_heads=4, n_kv_heads=1,
                  d_ff=32, dtype="float32")
    p = m.init_params(0)
    assert p["blocks"]["wk"].shape == (1, 32, 1 * 8)
    assert p["blocks"]["wq"].shape == (1, 32, 4 * 8)
    logits = m.apply(p, TOKS[:, :4] % 32)
    assert logits.shape == (2, 4, 32)


def test_moe_model_forward():
    m = DecoderLM(vocab_size=32, d_model=32, n_layers=2, n_heads=2, n_kv_heads=2,
                  d_ff=64, n_experts=4, dtype="float32")
    p = m.init_params(0)
    assert p["blocks"]["w1e"].shape == (2, 4, 32, 64)
    logits = m.apply(p, TOKS[:, :4] % 32)
    assert logits.shape == (2, 4, 32)
    assert np.isfinite(np.asarray(logits)).all()


def test_loss_decreases_single_chip(small_model):
    m, _ = small_model
    p = m.init_params(1)
    toks = jnp.asarray(np.random.RandomState(1).randint(0, 128, (4, 12)), jnp.int32)
    loss_grad = jax.jit(jax.value_and_grad(m.loss_fn))
    losses = []
    for _ in range(8):
        loss, g = loss_grad(p, toks)
        p = jax.tree_util.tree_map(lambda a, b: a - 0.1 * b, p, g)
        losses.append(float(loss))
    assert losses[-1] < losses[0]


# -- the burst's params (ISSUE 54): the q / k / v weights the compiled burst
# consumes contraction-minor are held that way once, beside the stored ones --


def _as(params, dt):
    return jax.tree_util.tree_map(lambda a: a.astype(dt), params)


def test_burst_params_hold_q_k_v_contraction_minor_and_share_the_rest(small_model):
    m, p = small_model
    bp = m.burst_params(p)
    assert sorted(bp["blocks"]) == sorted(
        {"wq_t", "wk_t", "wv_t"} | (set(p["blocks"]) - {"wq", "wk", "wv"}))
    for w in ("wq", "wk", "wv"):
        L, D, out = p["blocks"][w].shape
        assert bp["blocks"][w + "_t"].shape == (L, out, D)
        np.testing.assert_array_equal(
            bp["blocks"][w + "_t"], np.swapaxes(p["blocks"][w], 1, 2))
    # every other leaf is the stored array itself, not a copy
    assert all(bp[k] is p[k] for k in ("embed", "ln_f", "unembed"))
    assert all(bp["blocks"][k] is p["blocks"][k]
               for k in p["blocks"] if k not in ("wq", "wk", "wv"))
    # the stored tree is what it was
    assert "wq" in p["blocks"] and "wq_t" not in p["blocks"]


@pytest.mark.parametrize("path", ["step", "chunk"])
def test_the_burst_step_on_burst_params_is_the_step_on_params_bit_for_bit(path):
    """In the serving dtype (bfloat16) the logits and the rows written are
    equal bit for bit: the contraction is the same sum whichever axis of
    the weight it runs over. (In float32 the CPU's matmul accumulates in
    another order by layout: equal to rounding there.)"""
    m = DecoderLM(vocab_size=128, d_model=64, n_layers=2, n_heads=4,
                  n_kv_heads=2, d_ff=128, max_seq=64, dtype="bfloat16")
    p = _as(m.init_params(0), jnp.bfloat16)
    cache = m.cache_layers(3, 64)
    pos = jnp.asarray([3, 0, 10], jnp.int32)
    if path == "step":
        fn, toks = jax.jit(m.decode_step_ragged_list), TOKS[:1, :3].T
    else:   # speculation's verify window: ``_qkv`` takes the leaf it is given
        fn, toks = jax.jit(m.decode_chunk_ragged_list), TOKS[:, :3].T
    stored = fn(p, cache["k"], cache["v"], toks, pos)
    relaid = fn(m.burst_params(p), cache["k"], cache["v"], toks, pos)
    for a, b in zip(jax.tree_util.tree_leaves(stored),
                    jax.tree_util.tree_leaves(relaid)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
    assert np.asarray(stored[0], np.float32).any()


def test_the_burst_step_on_burst_params_in_float32_is_equal_to_rounding(small_model):
    m, p = small_model
    cache = m.cache_layers(2, 64)
    step = jax.jit(m.decode_step_ragged_list)
    pos = jnp.asarray([4, 9], jnp.int32)
    stored = step(p, cache["k"], cache["v"], TOKS[:, :1], pos)
    relaid = step(m.burst_params(p), cache["k"], cache["v"], TOKS[:, :1], pos)
    for a, b in zip(jax.tree_util.tree_leaves(stored),
                    jax.tree_util.tree_leaves(relaid)):
        np.testing.assert_allclose(a, b, atol=1e-5)


def test_under_a_serving_mesh_the_burst_takes_the_stored_params(small_model):
    m, p = small_model
    sharded = DecoderLM(**dataclasses.asdict(m.cfg))
    sharded._serving_mesh = object()    # armed: param_sharding names stored leaves
    assert sharded.burst_params(p) is p


@pytest.mark.parametrize("block", [
    "afmoe", "qwen3_next", "joyai_llm_flash", "sdar_moe", "lfm2_moe"])
def test_a_family_that_states_no_burst_layout_gets_the_object_it_gave(block):
    """The identity families' bursts are handed ``params`` itself: the
    program they always were (their bursts' HLO against the parent's:
    ``tools/burst_hlo_check.py --against``)."""
    from seldon_core_tpu.models.family import DecoderFamily, family_class

    cls = family_class(block)
    assert cls.burst_params is DecoderFamily.burst_params
    params = {"layers": [{"wq": object()}]}
    assert cls.burst_params(None, params) is params


def test_the_batcher_hands_its_bursts_the_derived_tree_and_a_swap_rederives_it():
    from seldon_core_tpu.serving.continuous import ContinuousBatcher

    kw = dict(vocab_size=128, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
              d_ff=128, max_seq=64, dtype="float32")
    m = DecoderLM(**kw)
    old, new = m.init_params(0), m.init_params(1)
    prompt = [3, 17, 42, 99, 7]
    fresh = ContinuousBatcher(m, new, slots=2, max_seq=64, prefill_buckets=(8,))
    try:
        want = fresh.generate(prompt, max_new_tokens=8)
    finally:
        fresh.close()
    b = ContinuousBatcher(m, old, slots=2, max_seq=64, prefill_buckets=(8,))
    try:
        relaid = 4 * sum(old["blocks"][w].size for w in ("wq", "wk", "wv"))
        assert b.stats["burst_params_relaid_bytes"] == relaid
        assert b._burst_params is not b.params
        np.testing.assert_array_equal(
            b._burst_params["blocks"]["wq_t"],
            np.swapaxes(old["blocks"]["wq"], 1, 2))
        before = b.generate(prompt, max_new_tokens=8)
        assert before != want
        assert b.request_weight_swap(new, version="v1").result(30.0) == "v1"
        np.testing.assert_array_equal(
            b._burst_params["blocks"]["wq_t"],
            np.swapaxes(new["blocks"]["wq"], 1, 2))
        assert b._burst_params["embed"] is b.params["embed"]
        assert b.stats["burst_params_relaid_bytes"] == relaid
        # tokens after the swap are the new weights'
        assert b.generate(prompt, max_new_tokens=8) == want
        # the burst executables keep what the jitted function answers
        assert b._burst_fn._cache_size() >= 1
    finally:
        b.close()
