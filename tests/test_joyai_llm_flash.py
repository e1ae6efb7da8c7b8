"""The joyai_llm_flash block (``models/joyai_llm_flash.py``) through the
batcher's own cache against the plain reference's full forward, at a small
size in float32: the dense layer and two expert layers, 16 experts of which
4 are held, latent rank 128 beside a rotary key of 16. Also: the absorbed
decode step is the expanded attention over the same cache; a lane admitted
beside live ones leaves them bit-equal; the share test; the router with a
bias; what the scheduler asks a model of its cache, with the other
families' answers unchanged; and the typed refusals. CPU only."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import joyai_llm_flash as reference
from seldon_core_tpu.models.joyai_llm_flash import rope_pairs
from seldon_core_tpu.models.llm import DecoderLM, UnsupportedByModel, _rope
from seldon_core_tpu.ops import experts
from seldon_core_tpu.ops.latent_attention import LATENT_BLOCK
from seldon_core_tpu.serving.continuous import ContinuousBatcher

SMALL = dict(
    block="joyai_llm_flash", vocab_size=256, d_model=128, n_layers=3,
    n_heads=4, n_kv_heads=4, head_dim=48, d_ff=256, max_seq=256,
    rope_theta=3.2e7, norm_eps=1e-6, dtype="float32", n_dense_layers=1,
    n_routed_experts=16, experts_per_tok=4, expert_width=64,
    n_shared_experts=1, route_scale=2.5, experts_held=(4, 4),
    q_lora_rank=64, kv_lora_rank=128, qk_nope_head_dim=32,
    qk_rope_head_dim=16, v_head_dim=32, residual_scale=0.5)
ROW = 256      # 128 + 16 -> two registers of lanes


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
    assert type(model).__name__ == "JoyaiLLMFlashLM"
    assert model.n_params() == sum(
        a.size for a in jax.tree_util.tree_leaves(params))
    # no layer reads a [S, KV, T, Dh] cache
    assert model.attention_kinds() == ()
    cache = model.cache_layers(4, 256)
    assert sorted(cache) == ["latent"] and len(cache["latent"]) == 3
    assert cache["latent"][0].shape == (4, 256, ROW)
    # a position: what holds something, and what is allocated
    assert model.latent_bytes_per_position() == (128 + 16) * 2
    assert model.kv_bytes_per_token() == 3 * (128 + 16) * 2
    assert model.cache_position_bytes(cache) == 3 * ROW * 4     # float32 here
    assert model.prefill_slab_bytes(8, 128) == 3 * 8 * 128 * ROW * 2
    assert len(model.position_layers(cache)) == 3
    assert not model.burst_reads_ragged(cache)                  # on the CPU
    # a step reads the held experts its live lanes are expected to touch
    assert model.step_param_bytes(64) > model.step_param_bytes(1)
    assert model.decode_bytes_per_token(100.0, 4) > model.decode_bytes_per_token(0.0, 4)
    assert model.dispatch_read_bytes("fused_burst", rows=4, live=2, k=8, bucket=128) \
        == 8 * (model.step_param_bytes(2) + 2 * 128 * model.kv_bytes_per_token())


def test_the_published_widths_give_the_issues_parameter_counts():
    """Attention 26.35 M a layer, one expert 4.72 M, a dense layer 70.4 M,
    an expert layer 1,239.6 M, the model 48.94 B: counted, not allocated."""
    whole = DecoderLM(
        block="joyai_llm_flash", vocab_size=129280, d_model=2048, n_layers=40,
        n_heads=32, n_kv_heads=32, head_dim=192, d_ff=7168, n_dense_layers=1,
        n_routed_experts=256, experts_per_tok=8, expert_width=768,
        n_shared_experts=1, q_lora_rank=1536, kv_lora_rank=512,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128)
    assert round(whole._attention_params() / 1e6, 2) == 26.35
    assert round(whole._layer_params(False, 0) / 1e6, 1) == 70.4
    assert round(whole._layer_params(True, 256) / 1e6, 1) == 1239.6
    assert round(whole.n_params() / 1e9, 2) == 48.94
    assert whole.latent_bytes_per_position() == 1152
    assert whole.cache_layers(1, 128)["latent"][0].shape == (1, 128, 640)


def test_rotary_turns_interleaved_pairs():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(2, 3, 5, 16)), jnp.float32)
    pos = jnp.arange(5) + 7
    out = np.asarray(rope_pairs(x, pos, 1e4))
    inv = 1.0 / (1e4 ** (np.arange(8) / 8))
    ang = np.asarray(pos)[:, None] * inv[None, :]
    a, b = np.asarray(x)[..., 0::2], np.asarray(x)[..., 1::2]
    np.testing.assert_allclose(out[..., 0::2], a * np.cos(ang) - b * np.sin(ang), atol=1e-5)
    np.testing.assert_allclose(out[..., 1::2], a * np.sin(ang) + b * np.cos(ang), atol=1e-5)
    # per-lane positions [B, T] give the same turn
    np.testing.assert_allclose(
        rope_pairs(x, jnp.broadcast_to(pos, (2, 5)), 1e4), out, atol=1e-6)
    # and it is the half-split rotary of the de-interleaved dims, which is
    # not the half-split rotary of the dims as they lie
    halves = jnp.concatenate([x[..., 0::2], x[..., 1::2]], -1)
    turned = np.asarray(_rope(halves, pos, 1e4))
    np.testing.assert_allclose(turned[..., :8], out[..., 0::2], atol=1e-5)
    assert np.abs(np.asarray(_rope(x, pos, 1e4)) - out).max() > 0.1
    # the reference turns the same pairs
    ref = reference._rotary(jnp.swapaxes(x[0], 0, 1), 1e4)      # [T, H, d]
    np.testing.assert_allclose(
        ref, np.swapaxes(np.asarray(rope_pairs(x[:1], jnp.arange(5), 1e4))[0], 0, 1),
        atol=1e-5)


def test_prefill_and_decode_through_the_batchers_cache_are_the_reference(
        served, batcher):
    """Prompts of unequal lengths padded to one bucket go through the
    batched prefill, the batcher's own ``insert_many`` puts their rows at
    lanes 0 and 2 of its cache, and four steps of ``decode_step_cache``
    (lane 1 idle, lane 3 never admitted) give the reference's logits and
    write the reference's rows."""
    model, params = served
    rng = np.random.default_rng(0)
    n = {0: 100, 2: 37}
    toks = {lane: rng.integers(0, 256, size=length + 4)
            for lane, length in n.items()}
    prompts = np.zeros((2, 128), np.int32)
    for row, lane in enumerate(n):
        prompts[row, :n[lane]] = toks[lane][:n[lane]]
    last = jnp.asarray([n[0] - 1, n[2] - 1], jnp.int32)
    logits, slab = jax.jit(lambda p, t, li: model.prefill(p, t, 128, li))(
        params, jnp.asarray(prompts), last)
    assert slab["latent"].shape == (3, 2, 128, ROW)
    assert not np.asarray(slab["latent"][..., 144:]).any()      # the padding
    cache, *_ = batcher._insert_many_fn(
        model.cache_layers(4, 256), slab, jnp.asarray([0, 2], jnp.int32),
        jnp.zeros((2,), jnp.int32), last + 1, jnp.zeros((2, 2), jnp.uint32),
        jnp.zeros((4,), jnp.int32), jnp.zeros((4,), jnp.int32),
        jnp.zeros((4, 2), jnp.uint32))
    want = {lane: reference.forward(
        params, model.cfg, toks[lane], list(range(n[lane] - 1, n[lane] + 4)))
        for lane in n}
    for row, lane in enumerate(n):
        np.testing.assert_allclose(logits[row], want[lane][0][0], atol=2e-4)
        for l in range(3):
            np.testing.assert_allclose(
                slab["latent"][l, row, :n[lane], :144],
                want[lane][3][l][:n[lane]], atol=2e-4)
    step = jax.jit(model.decode_step_cache)
    for i in range(4):
        pos = np.array([n[0] + i, 0, n[2] + i, 0])
        live = np.array([True, False, True, False])
        tokens = np.array([toks[0][n[0] + i], 0, toks[2][n[2] + i], 0])
        lens = np.where(live, pos + 1, 0)
        out, cache, counts = step(
            params, cache, jnp.asarray(tokens[:, None], jnp.int32),
            jnp.asarray(pos, jnp.int32), lens=jnp.asarray(lens, jnp.int32))
        for lane in n:
            np.testing.assert_allclose(out[lane], want[lane][0][1 + i], atol=2e-4)
            for l in range(3):
                np.testing.assert_allclose(
                    cache["latent"][l][lane, n[lane] + i, :144],
                    want[lane][3][l][n[lane] + i], atol=2e-4)
        touched, routed, layer_steps, held, read, alive, lane_steps = (
            np.asarray(counts).tolist())
        # 2 live lanes x 4 picks x 2 expert layers; over 3 latent layers
        # the lanes' lengths, rounded up to the block where they are read
        assert (routed, layer_steps, lane_steps) == (16, 2, 6)
        assert 0 < touched <= held <= routed
        assert alive == 3 * int(lens.sum())
        assert read == 3 * int(
                (-(-lens // LATENT_BLOCK) * LATENT_BLOCK).sum())


def _decode_step_expanded(model, params, cache, tokens, pos):
    """The decode step WITHOUT absorption: the new row written, every
    lane's keys and values expanded from its rows and attended to as the
    prefill does. ``(logits, cache)``."""
    from seldon_core_tpu.models.llm import _rms_norm
    from seldon_core_tpu.ops.latent_attention import latent_cache_write

    cfg = model.cfg
    r, rope = cfg.kv_lora_rank, cfg.qk_rope_head_dim
    x = model._embed_tokens(params, tokens)
    new = []
    for l, (p, routed) in enumerate(zip(params["layers"], model._routed)):
        a = _rms_norm(x, p["ln_in"], cfg.norm_eps)
        q_n, q_r, row = model._latent(p, a, pos[:, None])
        rows = latent_cache_write(cache["latent"][l], row[:, 0], pos)
        new.append(rows)
        k_n = jnp.einsum("btc,hnc->bhtn", rows[..., :r], p["w_uk"])
        v = jnp.einsum("btc,hcv->bhtv", rows[..., :r], p["w_uv"])
        s = (jnp.einsum("bhqn,bhtn->bhqt", q_n, k_n)
             + jnp.einsum("bhqr,btr->bhqt", q_r, rows[..., r:r + rope])
             ) * model._scale
        seen = jnp.arange(rows.shape[1])[None, :] <= pos[:, None]
        w = jax.nn.softmax(jnp.where(seen[:, None, None, :], s, -1e30), -1)
        x = x + model._attention_out(p, jnp.einsum("bhqt,bhtv->bhqv", w, v))
        x = model._ffn(p, x, routed, live=jnp.ones(pos.shape, bool))[0]
    return model._head(params, x), {"latent": new}


def test_the_absorbed_step_is_the_expanded_attention_over_the_same_cache(served):
    """Decode absorbs ``W_UK`` into the query and ``W_UV`` into the output
    and reads the latent itself; expanding every cached row into keys and
    values and attending to those gives the same logits and writes the same
    row."""
    model, params = served
    rng = np.random.default_rng(5)
    cache = jax.tree_util.tree_map(
        lambda a: jnp.asarray(rng.normal(size=a.shape), a.dtype).at[..., 144:].set(0),
        model.cache_layers(3, 128))
    tokens = jnp.asarray(rng.integers(0, 256, size=(3, 1)), jnp.int32)
    pos = jnp.asarray([5, 127, 64], jnp.int32)
    absorbed, c_a, _ = jax.jit(model.decode_step_cache)(params, cache, tokens, pos)
    expanded, c_e = jax.jit(lambda *a: _decode_step_expanded(model, *a))(
        params, cache, tokens, pos)
    np.testing.assert_allclose(absorbed, expanded, atol=2e-4)
    for a, e in zip(c_a["latent"], c_e["latent"]):
        np.testing.assert_allclose(a, e, atol=1e-5)


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
    assert stats["moe_layer_steps"] > 0 and stats["mla_lane_steps"] > 0
    assert 0.1 < stats["moe_rows_held"] / stats["moe_rows_routed"] < 0.45
    assert stats["mla_positions_live"] <= stats["mla_positions_read"]
    assert stats["mla_positions_read"] % LATENT_BLOCK == 0
    # a step writes one row a live lane in each of the three layers
    assert stats["kv_rows_written"] % 3 == 0 and stats["kv_rows_written"] > 0
    routed = stats["moe_prefill_pairs_routed"]
    assert routed == stats["prefill_tokens"] * 4 * 2 > 0
    assert 0 < stats["moe_prefill_pairs_moved"] <= routed


def test_a_lane_admitted_beside_live_lanes_leaves_them_bit_equal(served, batcher):
    model, params = served
    rng = np.random.default_rng(2)
    cache = jax.tree_util.tree_map(
        lambda a: jnp.asarray(rng.normal(size=a.shape), a.dtype),
        model.cache_layers(4, 256))
    before = jax.tree_util.tree_map(np.asarray, cache)
    prompt = jnp.asarray(rng.integers(0, 256, size=(1, 128)), jnp.int32)
    _, one = jax.jit(lambda p, t: model.prefill(p, t, 128, jnp.asarray([90])))(
        params, prompt)
    new, *_ = batcher._insert_fn(
        cache, one, 2, jnp.int32(1), jnp.int32(91), jnp.zeros((2,), jnp.uint32),
        jnp.zeros((4,), jnp.int32), jnp.zeros((4,), jnp.int32),
        jnp.zeros((4, 2), jnp.uint32))
    for l, (was, now) in enumerate(zip(before["latent"], new["latent"])):
        now = np.asarray(now)
        for lane in (0, 1, 3):
            np.testing.assert_array_equal(now[lane], was[lane])
        # the prompt's bucket of rows; the lane's tail past it stays
        np.testing.assert_array_equal(now[2, :128], one["latent"][l][0])
        np.testing.assert_array_equal(now[2, 128:], was[2, 128:])


def test_the_eight_shares_and_the_shared_expert_once_are_the_uncut_layer(served):
    """The share test: a layer's 16 experts divided over eight chips. Each
    share routes over all 16 and computes its own 2; the eight routed parts
    and the shared expert counted once add up to what the reference gives
    for the whole layer, prefill (grouped) and decode (touched) alike."""
    whole = DecoderLM(**dict(SMALL, experts_held=None))
    p = whole.init_params(5)["layers"][1]
    cfg = whole.cfg
    rng = np.random.default_rng(7)
    h = jnp.asarray(rng.normal(size=(6, 1, 128)), jnp.float32)
    m = reference._norm(h[:, 0], p["ln_post"], cfg.norm_eps)
    with jax.default_matmul_precision("highest"):
        uncut, _, _ = reference._routed_ffn(m, p, cfg, None, "")
        shared_once, _, _ = reference._routed_ffn(m, p, cfg, (0, 0), "")
    live = jnp.asarray([True] * 5 + [False])
    for how in (None, live):
        parts = []
        for share in range(8):
            model = DecoderLM(**dict(SMALL, experts_held=(2 * share, 2)))
            mine = dict(p, **{n: p[n][2 * share:2 * share + 2]
                              for n in ("we1", "we3", "we2")})
            out, picks, counts = model._ffn(mine, h, True, live=how)
            # each share's output is input + its routed part + the shared
            parts.append(np.asarray(out - h)[:, 0] - np.asarray(shared_once))
            if how is not None:
                here = (picks[:, 0] >= 2 * share) & (picks[:, 0] < 2 * share + 2)
                assert int(counts[2]) == int((here & live[:, None]).sum())
                assert int(counts[1]) == 5 * 4
        rows = slice(0, 5) if how is not None else slice(None)
        np.testing.assert_allclose(
            (sum(parts) + np.asarray(shared_once))[rows], np.asarray(uncut)[rows],
            atol=2e-5)


def test_the_router_picks_by_score_plus_bias_and_weighs_by_score():
    """noaux_tc at one group: a non-zero bias moves the picks and leaves
    the weights the picked experts' own scores, normed to the scale."""
    rng = np.random.default_rng(8)
    x = jnp.asarray(rng.normal(size=(32, 64)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(64, 16)), jnp.float32)
    bias = jnp.asarray(rng.normal(size=(16,)), jnp.float32)
    picks, w = experts.route(x, router, bias, 4, 2.5)
    s = jax.nn.sigmoid(x @ router)
    np.testing.assert_array_equal(picks, jax.lax.top_k(s + bias, 4)[1])
    plain, _ = experts.route(x, router, jnp.zeros((16,)), 4, 2.5)
    assert (np.sort(picks, -1) != np.sort(plain, -1)).any()
    sel = jnp.take_along_axis(s, picks, -1)
    np.testing.assert_allclose(w, sel / sel.sum(-1, keepdims=True) * 2.5, rtol=1e-5)
    np.testing.assert_allclose(w.sum(-1), 2.5, rtol=1e-5)
    # the reference's router agrees, bias and all
    cfg = DecoderLM(**dict(SMALL, experts_held=None)).cfg
    p = {"router": router, "expert_bias": bias,
         "we1": jnp.zeros((16, 64, 8)), "we3": jnp.zeros((16, 64, 8)),
         "we2": jnp.zeros((16, 8, 64)), "ws1": jnp.zeros((64, 8)),
         "ws3": jnp.zeros((64, 8)), "ws2": jnp.zeros((8, 64))}
    with jax.default_matmul_precision("highest"):
        _, own, scores = reference._routed_ffn(x, p, cfg, None, "")
    np.testing.assert_array_equal(np.sort(own, -1), np.sort(picks, -1))
    np.testing.assert_allclose(scores, s + bias, atol=1e-5)


def test_an_expert_width_of_768_goes_through_the_decode_kernel():
    """The touched-expert kernel slices an expert's width into whole
    registers: 512 at the widths the benchmark had, 384 at 768."""
    rng = np.random.default_rng(9)
    x = jnp.asarray(rng.normal(size=(16, 128)), jnp.float32) * 0.3
    w1 = jnp.asarray(rng.normal(size=(4, 128, 768)), jnp.float32) * 0.1
    w2 = jnp.asarray(rng.normal(size=(4, 768, 128)), jnp.float32) * 0.1
    picks = jnp.asarray(rng.integers(0, 4, size=(16, 2)), jnp.int32)
    weights = jnp.asarray(rng.uniform(size=(16, 2)), jnp.float32)
    live = jnp.asarray([True] * 12 + [False] * 4)
    ids, n = experts.touched_experts(picks, live, 4)
    got = experts.touched_experts_ffn(
        x, picks, jnp.where(live[:, None], weights, 0.0), ids, n, w1, w1, w2,
        interpret=True)
    want, _ = experts.grouped_experts(
        x, picks, jnp.where(live[:, None], weights, 0.0), w1, w1, w2)
    np.testing.assert_allclose(got, want, atol=1e-4)


def test_what_the_scheduler_asks_of_a_cache_is_what_it_spelt_before():
    """``_chunk8_ok``'s slab, a position's bytes and the ragged read's rule
    were spelt as ``KV x Dh`` in the batcher; the model answers now, and
    for the dense, afmoe and qwen3_next blocks the answers are the old
    arithmetic's."""
    from seldon_core_tpu.ops.decode_attention import reads_ragged

    kinds = (["linear_attention"] * 3 + ["full_attention"])
    families = {
        "llama": dict(vocab_size=128, d_model=64, n_layers=3, n_heads=4,
                      n_kv_heads=2, d_ff=128, max_seq=128, dtype="bfloat16"),
        "afmoe": dict(block="afmoe", vocab_size=128, d_model=64, n_layers=2,
                      n_heads=4, n_kv_heads=2, head_dim=32, d_ff=128,
                      max_seq=128, dtype="bfloat16", n_dense_layers=1,
                      n_routed_experts=8, experts_per_tok=2, expert_width=32,
                      n_shared_experts=1),
        "qwen3_next": dict(block="qwen3_next", vocab_size=128, d_model=64,
                           n_layers=4, n_heads=4, n_kv_heads=2, head_dim=32,
                           max_seq=128, dtype="bfloat16", layer_types=kinds,
                           n_routed_experts=8, experts_per_tok=2,
                           expert_width=32, shared_expert_width=32,
                           linear_key_heads=2, linear_value_heads=4,
                           linear_key_dim=16, linear_value_dim=16,
                           linear_conv_kernel=4, partial_rotary_factor=0.5),
    }
    for name, kw in families.items():
        model = DecoderLM(**kw)
        cfg = model.cfg
        cache = model.cache_layers(4, 128)
        assert model.cache_position_bytes(cache) == 2 * sum(
            layer.dtype.itemsize * layer.shape[1] * layer.shape[3]
            for layer in cache["k"]), name
        assert len(model.position_layers(cache)) == 2 * len(cache["k"]), name
        for bucket in (128, 4096):
            assert model.prefill_slab_bytes(8, bucket) == (
                2 * cfg.n_layers * 8 * cfg.n_kv_heads * bucket * cfg.head_dim * 2)
        layer0 = cache["k"][0]
        assert model.burst_reads_ragged(cache) == reads_ragged(
            "cpu", (4, cfg.n_heads, 1, layer0.shape[3]), layer0.shape,
            (jnp.dtype(cfg.dtype), layer0.dtype, cache["v"][0].dtype), None)
        b = ContinuousBatcher(model, model.init_params(0), slots=4, max_seq=128)
        try:
            assert b._kv_key_bytes == model.cache_position_bytes(b._cache)
            assert b._position_layers == 2 * len(b._cache["k"])
            assert b._chunk8_ok(4096) and not b._ragged_read
        finally:
            b.close()


@pytest.mark.parametrize("setting", [
    {"prefix_cache_hbm_bytes": 1 << 20}, {"prefill_chunk": 64},
    {"hbm_ledger_bytes": 1 << 30}, {"host_kv_tier_bytes": 1 << 20},
    {"swap_drain_ms": 100}])
def test_what_copies_k_and_v_by_name_is_refused_at_load(served, setting):
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
        ContinuousBatcher(model, params, slots=2, max_seq=256,
                          draft_model=model, draft_params=params,
                          speculate_tokens=2)
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
        DecoderLM(**dict(SMALL, kv_lora_rank=0))
    with pytest.raises(ValueError):
        DecoderLM(**dict(SMALL, qk_rope_head_dim=15))
    with pytest.raises(ValueError):
        DecoderLM(**dict(SMALL, experts_held=(14, 4)))
    with pytest.raises(ValueError):
        DecoderLM(**dict(SMALL, expert_width=0))
