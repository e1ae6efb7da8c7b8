"""The qwen3_next block (``models/qwen3_next.py``) through the batcher's own
cache against the plain reference's full forward, at a small size in
float32: two periods of (linear, linear, linear, full), 16 experts of which
4 are held, convolution width 4. Also what the cache's per-kind layout
promises (a lane admitted beside live ones leaves them bit-equal, an idle
lane is untouched, a k/v-only model inserts as it did), the share test, and
the typed refusals. CPU only."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from benchmark.reference import qwen3_next as reference
from seldon_core_tpu.models.llm import DecoderLM, UnsupportedByModel
from seldon_core_tpu.ops import experts
from seldon_core_tpu.serving.continuous import ContinuousBatcher

KINDS = (["linear_attention"] * 3 + ["full_attention"]) * 2
SMALL = dict(
    block="qwen3_next", vocab_size=256, d_model=128, n_layers=8, n_heads=4,
    n_kv_heads=2, head_dim=64, max_seq=256, rope_theta=1e7, norm_eps=1e-6,
    dtype="float32", layer_types=KINDS, n_routed_experts=16,
    experts_per_tok=4, expert_width=64, shared_expert_width=64,
    experts_held=(4, 4), linear_key_heads=2, linear_value_heads=4,
    linear_key_dim=32, linear_value_dim=32, linear_conv_kernel=4,
    partial_rotary_factor=0.25, residual_scale=0.5)


@pytest.fixture(scope="module")
def served():
    model = DecoderLM(**SMALL)
    return model, model.init_params(3)


@pytest.fixture(scope="module")
def batcher(served):
    model, params = served
    b = ContinuousBatcher(model, params, slots=4, max_seq=256)
    yield b
    b.close()


def test_the_block_is_built_through_decoderlm_and_counts_its_parameters(served):
    model, params = served
    assert type(model).__name__ == "Qwen3NextLM"
    assert model.n_params() == sum(
        a.size for a in jax.tree_util.tree_leaves(params))
    assert model.attention_kinds() == ((2, None),)
    # a layer without keys allocates none: 2 k/v pairs, 6 states and tails
    cache = model.init_cache(4, 256)
    assert {n: len(v) for n, v in cache.items()} == {
        "k": 2, "v": 2, "conv": 6, "state": 6}
    assert cache["k"][0].shape == (4, 2, 256, 64)
    assert cache["state"][0].shape == (4, 4, 32, 32)
    assert cache["state"][0].dtype == jnp.float32
    assert cache["conv"][0].shape == (4, 3, 2 * 2 * 32 + 4 * 32)
    assert model.kv_bytes_per_token() == 2 * 2 * 2 * 64 * 2


def test_prefill_and_decode_through_the_batchers_cache_are_the_reference(
        served, batcher):
    """Prompts of unequal lengths padded to one bucket go through the
    batched prefill, the batcher's own ``insert_many`` puts their rows at
    lanes 0 and 2 of its cache, and four steps of ``decode_step_cache``
    (lane 1 idle, lane 3 never admitted) give the reference's logits."""
    model, params = served
    rng = np.random.default_rng(0)
    n = {0: 100, 2: 37}          # under the 128 bucket, on both sides of 64
    toks = {lane: rng.integers(0, 256, size=length + 4)
            for lane, length in n.items()}
    prompts = np.zeros((2, 128), np.int32)
    for row, lane in enumerate(n):
        prompts[row, :n[lane]] = toks[lane][:n[lane]]
    last = jnp.asarray([n[0] - 1, n[2] - 1], jnp.int32)
    logits, slab = jax.jit(lambda p, t, li: model.prefill(p, t, 128, li))(
        params, jnp.asarray(prompts), last)
    cache, *_ = batcher._insert_many_fn(
        model.init_cache(4, 256), slab, jnp.asarray([0, 2], jnp.int32), jnp.zeros((2,), jnp.int32),
        last + 1, jnp.zeros((2, 2), jnp.uint32), jnp.zeros((4,), jnp.int32),
        jnp.zeros((4,), jnp.int32), jnp.zeros((4, 2), jnp.uint32))
    want = {lane: reference.logits(
        params, model.cfg, toks[lane], list(range(n[lane] - 1, n[lane] + 4)))
        for lane in n}
    for row, lane in enumerate(n):
        np.testing.assert_allclose(logits[row], want[lane][0], atol=2e-4)
    kept = lambda c: jax.tree_util.tree_map(  # noqa: E731
        lambda a: np.asarray(a[1]), {n: c[n] for n in ("conv", "state")})
    idle = kept(cache)
    step = jax.jit(model.decode_step_cache)
    for i in range(4):
        pos = np.array([n[0] + i, 0, n[2] + i, 0])
        live = np.array([True, False, True, False])
        tokens = np.array([toks[0][n[0] + i], 0, toks[2][n[2] + i], 0])
        out, cache, counts = step(
            params, cache, jnp.asarray(tokens[:, None], jnp.int32),
            jnp.asarray(pos, jnp.int32),
            lens=jnp.asarray(np.where(live, pos + 1, 0), jnp.int32))
        for lane in n:
            np.testing.assert_allclose(out[lane], want[lane][1 + i], atol=2e-4)
        touched, routed, layer_steps, held, lane_steps = np.asarray(counts)
        # 2 live lanes x 4 picks x 8 layers; a state update a live lane
        # and linear layer; what is held is touched, and no more than that
        assert (routed, layer_steps, lane_steps) == (64, 8, 12)
        assert 0 < touched <= held <= routed
    # the idle lane's state and tail: bit for bit what they were (off a
    # TPU the scatter still writes an idle lane's key row, at a position
    # no read admits: ops/decode_attention.py)
    after = kept(cache)
    for a, b in zip(jax.tree_util.tree_leaves(idle),
                    jax.tree_util.tree_leaves(after)):
        np.testing.assert_array_equal(a, b)


def test_served_requests_are_the_references_greedy_tokens(served, batcher):
    """Through ``submit``: admission in a wave beside lanes that are
    decoding, the fused burst, lanes freed and taken again."""
    model, params = served
    batcher.start()
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 256, size=n).tolist()
               for n in (5, 70, 100, 33, 129, 17)]
    futures = [batcher.submit(p, max_new_tokens=6) for p in prompts]
    for prompt, future in zip(prompts, futures):
        full = list(future.result(timeout=600))
        assert full[:len(prompt)] == prompt and len(full) == len(prompt) + 6
        want = reference.logits(params, model.cfg, np.array(full),
                                list(range(len(prompt) - 1, len(full) - 1)))
        assert full[len(prompt):] == want.argmax(-1).tolist()
    stats = batcher.stats
    assert stats["moe_layer_steps"] > 0 and stats["gdn_lane_steps"] > 0
    assert stats["moe_rows_held"] < stats["moe_rows_routed"]
    # a quarter of the experts held, near-uniform routing
    assert 0.1 < stats["moe_rows_held"] / stats["moe_rows_routed"] < 0.45


def test_a_lane_admitted_beside_live_lanes_leaves_them_bit_equal(served, batcher):
    model, params = served
    rng = np.random.default_rng(2)
    cache = jax.tree_util.tree_map(
        lambda a: jnp.asarray(rng.normal(size=a.shape), a.dtype),
        model.init_cache(4, 256))
    before = jax.tree_util.tree_map(np.asarray, cache)
    prompt = jnp.asarray(rng.integers(0, 256, size=(1, 128)), jnp.int32)
    _, one = jax.jit(lambda p, t: model.prefill(p, t, 128, jnp.asarray([90])))(
        params, prompt)
    new, *_ = batcher._insert_fn(
        cache, one, 2, jnp.int32(1), jnp.int32(91), jnp.zeros((2,), jnp.uint32),
        jnp.zeros((4,), jnp.int32), jnp.zeros((4,), jnp.int32),
        jnp.zeros((4, 2), jnp.uint32))
    for name in before:
        for l, (was, now) in enumerate(zip(before[name], new[name])):
            now = np.asarray(now)
            for lane in (0, 1, 3):
                np.testing.assert_array_equal(now[lane], was[lane])
            if name in ("k", "v"):
                # the prompt's bucket of keys; the lane's tail past it stays
                np.testing.assert_array_equal(now[2, :, :128], one[name][l][0])
                np.testing.assert_array_equal(now[2, :, 128:], was[2, :, 128:])
            else:
                np.testing.assert_array_equal(now[2], one[name][l][0])


def test_a_k_v_cache_is_inserted_as_it_was_before_the_cache_had_kinds():
    """The two places that spelt the cache's keys by hand go over the
    pytree now: for a model whose cache is keys and values the result is
    the one the spelt-out update gave, bit for bit."""
    model = DecoderLM(vocab_size=128, d_model=64, n_layers=3, n_heads=4,
                      n_kv_heads=2, d_ff=128, max_seq=64, dtype="float32")
    params = model.init_params(0)
    b = ContinuousBatcher(model, params, slots=4, max_seq=64)
    try:
        rng = np.random.default_rng(0)
        cache = jax.tree_util.tree_map(
            lambda a: jnp.asarray(rng.normal(size=a.shape), a.dtype), b._cache)
        assert sorted(cache) == ["k", "v"] and len(cache["k"]) == 3
        slab = {n: jnp.asarray(rng.normal(size=(3, 2, 2, 32, 16)), jnp.float32)
                for n in ("k", "v")}
        regs = (jnp.zeros((4,), jnp.int32), jnp.zeros((4,), jnp.int32),
                jnp.zeros((4, 2), jnp.uint32))
        want = {n: [lax.dynamic_update_slice(layer, slab[n][l, :1], (3, 0, 0, 0))
                    for l, layer in enumerate(cache[n])] for n in ("k", "v")}
        copy = jax.tree_util.tree_map(jnp.array, cache)
        got, *_ = b._insert_fn(
            copy, {n: s[:, :1] for n, s in slab.items()}, 3, jnp.int32(1),
            jnp.int32(9), jnp.zeros((2,), jnp.uint32), *regs)
        for n in ("k", "v"):
            for a, c in zip(want[n], got[n]):
                np.testing.assert_array_equal(a, c)
        want = {n: [lax.dynamic_update_slice(
            lax.dynamic_update_slice(layer, slab[n][l, :1], (2, 0, 0, 0)),
            slab[n][l, 1:], (0, 0, 0, 0)) for l, layer in enumerate(cache[n])]
            for n in ("k", "v")}
        copy = jax.tree_util.tree_map(jnp.array, cache)
        got, *_ = b._insert_many_fn(
            copy, slab, jnp.asarray([2, 0], jnp.int32),
            jnp.zeros((2,), jnp.int32), jnp.zeros((2,), jnp.int32),
            jnp.zeros((2, 2), jnp.uint32), *regs)
        for n in ("k", "v"):
            for a, c in zip(want[n], got[n]):
                np.testing.assert_array_equal(a, c)
    finally:
        b.close()


def test_the_four_shares_and_the_shared_expert_once_are_the_uncut_layer(served):
    """The share test: a layer's experts divided over four chips. Each
    share routes over all 16 and computes its own 4; the four routed parts
    and the shared expert counted once add up to what the reference gives
    for the whole layer, prefill (grouped) and decode (touched) alike."""
    whole = DecoderLM(**dict(SMALL, experts_held=None))
    p = whole.init_params(5)["layers"][0]
    cfg = whole.cfg
    rng = np.random.default_rng(7)
    h = jnp.asarray(rng.normal(size=(6, 1, 128)), jnp.float32)
    m = reference._norm(h[:, 0], p["ln_post"], cfg.norm_eps)
    with jax.default_matmul_precision("highest"):
        uncut, _, _ = reference._moe(m, p, cfg, None, "")
        shared_once, _, _ = reference._moe(m, p, cfg, (0, 0), "")
    live = jnp.asarray([True] * 5 + [False])
    for how in (None, live):
        parts = []
        for share in range(4):
            model = DecoderLM(**dict(SMALL, experts_held=(4 * share, 4)))
            mine = dict(p, **{n: p[n][4 * share:4 * share + 4]
                              for n in ("we1", "we3", "we2")})
            out, picks, counts = model._moe(mine, h, live=how)
            # each share's output is input + its routed part + the shared
            parts.append(np.asarray(out - h)[:, 0] - np.asarray(shared_once))
            if how is not None:
                here = (picks[:, 0] >= 4 * share) & (picks[:, 0] < 4 * share + 4)
                assert int(counts[2]) == int((here & live[:, None]).sum())
                assert int(counts[1]) == 5 * 4
        rows = slice(0, 5) if how is not None else slice(None)
        np.testing.assert_allclose(
            (sum(parts) + np.asarray(shared_once))[rows], np.asarray(uncut)[rows],
            atol=1e-5)


def test_experts_route_by_softmax_and_drop_what_is_held_elsewhere():
    rng = np.random.default_rng(8)
    x = jnp.asarray(rng.normal(size=(16, 128)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(128, 32)), jnp.float32)
    picks, w = experts.route(x, router, None, 4, 1.0, score="softmax")
    probs = jax.nn.softmax(x @ router, -1)
    np.testing.assert_array_equal(picks, lax.top_k(probs, 4)[1])
    sel = jnp.take_along_axis(probs, picks, -1)
    np.testing.assert_allclose(w, sel / sel.sum(-1, keepdims=True), rtol=1e-5)
    with pytest.raises(ValueError):
        experts.route(x, router, None, 4, 1.0, score="tanh")
    local, lw = experts.localise(picks, w, (8, 8), 8)
    here = (picks >= 8) & (picks < 16)
    np.testing.assert_array_equal(local, jnp.where(here, picks - 8, 8))
    assert not lw[~here].any() and jnp.array_equal(lw[here], w[here])
    with pytest.raises(ValueError):
        experts.localise(picks, w, (8, 8), 4)
    # the touched experts are those of the held a live lane picked
    w1 = jnp.asarray(rng.normal(size=(8, 128, 128)), jnp.float32) * 0.1
    w2 = jnp.asarray(rng.normal(size=(8, 128, 128)), jnp.float32) * 0.1
    live = jnp.asarray([True] * 12 + [False] * 4)
    y, touched, routed = experts.decode_experts(
        x, picks, w, live, w1, w1, w2, held=(8, 8))
    mine = np.asarray(picks)[:12]
    assert int(touched) == len(np.unique(mine[(mine >= 8) & (mine < 16)]))
    assert int(routed) == 12 * 4
    want = np.zeros((16, 128), np.float32)
    for r in range(12):
        for e, we in zip(np.asarray(picks)[r], np.asarray(w)[r]):
            if 8 <= e < 16:
                a = x[r] @ w1[e - 8]
                want[r] += we * np.asarray((jax.nn.silu(a) * a) @ w2[e - 8])
    np.testing.assert_allclose(y, want, atol=1e-4)
    np.testing.assert_allclose(
        experts.grouped_experts(x, picks, w, w1, w1, w2, held=(8, 8))[:12],
        want[:12], atol=1e-4)


@pytest.mark.parametrize("setting", [
    {"prefix_cache_hbm_bytes": 1 << 20}, {"prefill_chunk": 64},
    {"hbm_ledger_bytes": 1 << 30}, {"host_kv_tier_bytes": 1 << 20},
    {"swap_drain_ms": 100}])
def test_what_needs_a_state_snapshot_is_refused_at_load(served, setting):
    model, params = served
    with pytest.raises(UnsupportedByModel):
        ContinuousBatcher(model, params, slots=2, max_seq=256, **setting)


def test_refusals_name_their_reason_and_requests_are_refused_where_they_come_in(
        served, batcher):
    model, params = served
    assert set(model.serving_refuses) == {
        "speculation", "mesh", "kv_tier", "prefix_cache", "chunked_prefill",
        "preemption", "migration"}
    for feature in model.serving_refuses:
        with pytest.raises(UnsupportedByModel, match=feature):
            model.check_serves(**{feature: True})
    model.check_serves(**{f: False for f in model.serving_refuses})
    with pytest.raises(UnsupportedByModel):
        batcher.submit_checkpoint({"prompt": [1, 2, 3], "emitted": [4]})
    with pytest.raises(UnsupportedByModel):
        batcher.export_prefill([1, 2, 3])
    with pytest.raises(UnsupportedByModel):
        batcher.admit_remote({"tokens": [1, 2, 3]}, {})
    for call in (lambda: model.decode_step_ragged_list(params, [], [], None, None),
                 lambda: model.prefill_chunk(params, None, None, 0, 0),
                 lambda: model.prefill_with_prefix(params, None, None, 0),
                 lambda: model.decode_chunk_ragged_list(params, [], [], None, None),
                 lambda: model.loss_fn(params, None),
                 lambda: model.param_sharding(None, params)):
        with pytest.raises(UnsupportedByModel):
            call()
    with pytest.raises(ValueError):
        DecoderLM(**dict(SMALL, layer_types=KINDS[:5]))
    with pytest.raises(ValueError):
        DecoderLM(**dict(SMALL, experts_held=(14, 4)))
    with pytest.raises(ValueError):
        DecoderLM(**dict(SMALL, partial_rotary_factor=0.0))


def test_the_dense_and_afmoe_blocks_take_none_of_the_new_arguments():
    """What the other families trace must not change: ``held`` reaches the
    expert ops as a Python None and ``route`` scores by the sigmoid."""
    import inspect

    assert inspect.signature(experts.route).parameters["score"].default == "sigmoid"
    for fn in (experts.grouped_experts, experts.decode_experts):
        assert inspect.signature(fn).parameters["held"].default is None
    dense = DecoderLM(vocab_size=64, d_model=32, n_layers=1, n_heads=2,
                      n_kv_heads=1, d_ff=64)
    assert not dense.serving_refuses
    # the one step the bursts call is, for a k/v cache, the list step
    # itself: the same arrays in the same order, nothing traced beside it
    import jax
    import jax.numpy as jnp

    params = dense.init_params(0)
    cache = dense.cache_layers(2, 16)
    assert sorted(cache) == ["k", "v"] and len(cache["k"]) == 1
    tok, pos = jnp.ones((2, 1), jnp.int32), jnp.array([3, 5], jnp.int32)
    a = jax.make_jaxpr(lambda c: dense.decode_step_cache(
        params, c, tok, pos, lens=pos + 1))(cache)
    b = jax.make_jaxpr(lambda c: dense.decode_step_ragged_list(
        params, c["k"], c["v"], tok, pos, lens=pos + 1))(cache)
    assert str(a) == str(b)
