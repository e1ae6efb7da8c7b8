"""The jamba block (AI21-Jamba2-3B's layers) at a small size on the CPU,
seeded random weights, against the benchmark's plain reference: batched
prefill of right-padded prompts and then decode through the batcher's cache
against the reference's ONE full forward (logits, the state itself, the
convolution's tails, K and V rows), idle lanes among live ones, the layers'
order and runs, the sizes at the published widths, the typed refusals. The
ops it brought: ``tests/test_selective_scan.py``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import jamba as reference
from seldon_core_tpu.models.llm import DecoderLM, UnsupportedByModel
from seldon_core_tpu.serving.continuous import ContinuousBatcher

# a period of 4 with one attention layer: runs of 1, 3 and 2 Mamba layers
# around attention layers 1 and 5; a state of 16, a rank above 1, a bias
SMALL = dict(
    block="jamba", vocab_size=256, d_model=64, n_layers=8, n_heads=4,
    n_kv_heads=1, head_dim=128, d_ff=128, max_seq=512, norm_eps=1e-6,
    dtype="float32", attn_layer_period=4, attn_layer_offset=1,
    mamba_d_state=16, mamba_d_conv=4, mamba_dt_rank=8, mamba_expand=2,
    residual_scale=0.3)
PUBLISHED = dict(
    block="jamba", vocab_size=65536, d_model=2560, n_layers=28, n_heads=20,
    n_kv_heads=1, d_ff=8192, max_seq=8192, norm_eps=1e-6,
    attn_layer_period=14, attn_layer_offset=7, mamba_d_state=16,
    mamba_d_conv=4, mamba_dt_rank=160, mamba_expand=2)
BLOCK = 256     # the decode kernel's block at one KV head of 128


@pytest.fixture(scope="module")
def served():
    model = DecoderLM(**SMALL)
    return model, model.init_params(3)


def test_the_block_is_built_through_decoderlm_and_counts_its_parameters(served):
    model, params = served
    assert type(model).__name__ == "JambaLM"
    assert model.n_params() == sum(
        a.size for a in jax.tree_util.tree_leaves(params))
    assert model._segments == (("mamba", 0, 1), ("attn", 0, 1),
                               ("mamba", 1, 3), ("attn", 1, 1), ("mamba", 4, 2))
    assert [run["w_in"].shape for run in params["runs"]] == [
        (1, 64, 256), (3, 64, 256), (2, 64, 256)]
    assert len(params["attn"]) == 2
    # 2 attention layers, not 8: the Mamba layers read no positions
    assert model.attention_kinds() == ((2, None),)
    cache = model.cache_layers(4, 512)
    assert {n: len(v) for n, v in cache.items()} == {
        "k": 2, "v": 2, "conv": 1, "state": 1}
    assert cache["k"][0].shape == (4, 1, 512, 128)
    # ONE array a kind over all 6 Mamba layers, the lane first
    assert cache["conv"][0].shape == (4, 6, 3, 128)
    assert cache["state"][0].shape == (4, 6, 16, 128)
    assert cache["state"][0].dtype == jnp.float32
    per_position = model.cache_position_bytes(cache)
    assert per_position == 2 * 2 * 128 * 4 and len(model.position_layers(cache)) == 4
    fixed = 6 * (16 * 128 * 4 + 3 * 128 * 4)
    lane_bytes = model.lane_cache_bytes(cache)
    assert (lane_bytes(0), lane_bytes(1), lane_bytes(100)) == (
        0, per_position + fixed, 100 * per_position + fixed)
    assert model.admissions_per_turn() == 0 and model.block_tokens() == 1
    assert model.burst_params(params) is params


def test_the_published_sizes():
    """Nothing is drawn: the arithmetic of the published widths."""
    model = DecoderLM(**PUBLISHED)
    assert model.cfg.head_dim == 128
    assert model._runs == (7, 13, 6) and model._n_full == 2
    assert [i for i, a in enumerate(model._attn) if a] == [7, 21]
    assert model.n_params() == 3_029_337_472
    assert model.state_bytes_per_lane_and_layer() == 327_680 + 30_720
    assert model.kv_bytes_per_token() == 1024
    shapes = jax.eval_shape(lambda: model.cache_layers(192, 8192))
    assert shapes["state"][0].shape == (192, 26, 16, 5120)
    assert shapes["conv"][0].shape == (192, 26, 3, 5120)
    assert sum(a.size * a.dtype.itemsize
               for a in jax.tree_util.tree_leaves(shapes)) == 192 * (
                   26 * 358_400 + 8192 * 1024)
    # a lane's fixed cost is the larger term: 9.3 MB against 1 KB a position
    assert model.prefill_slab_bytes(1, 1024) == 1024 * 1024 + 26 * 358_400


@pytest.mark.parametrize("bad", [
    dict(n_routed_experts=4), dict(attn_layer_offset=4), dict(mamba_d_conv=1),
    dict(n_heads=3, n_kv_heads=2)])
def test_a_configuration_the_block_cannot_serve_is_refused(bad):
    with pytest.raises(ValueError):
        DecoderLM(**dict(SMALL, **bad))


def test_apply_is_the_references_forward(served):
    model, params = served
    tokens = np.random.default_rng(0).integers(0, 256, size=40)
    want = reference.logits(params, model.cfg, tokens, list(range(40)))
    got = model.apply(params, jnp.asarray(tokens)[None])[0]
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-4)


# lane -> tokens it holds before its first step: shorter than the
# convolution (1, 3), as long (4), and on both sides of the kernel's block
LENGTHS = {0: 1, 1: 3, 2: 4, 4: BLOCK - 1, 5: BLOCK, 6: 300}
LANES, STEPS = 8, 5


def test_prefill_then_decode_through_the_cache_is_one_forward(served):
    """Rows of different lengths in ONE padded batch leave each its own
    state and tail; the steps then go on from them, idle lanes among the
    live ones: logits, state, tails and rows against the reference's one
    forward over the longest lane's tokens."""
    model, params = served
    total = max(LENGTHS.values()) + STEPS
    tokens = np.random.default_rng(1).integers(0, 256, size=total)
    lanes = sorted(LENGTHS)
    lens = np.array([LENGTHS[j] for j in lanes])
    # every position any lane's logits are taken at, and where its state is
    ends = lens[:, None] + np.arange(STEPS)[None]              # [lanes, steps]
    ref, ref_kv, ref_a, ref_states = reference.forward(
        params, model.cfg, tokens, list(range(total)),
        state_at=(lens - 1).tolist() + (lens + STEPS - 1).tolist())
    prompts = np.zeros((len(lanes), 512), np.int32)
    for i, n in enumerate(lens):
        prompts[i, :n] = tokens[:n]
    logits, slab, counts = jax.jit(
        lambda p, t, last: model.prefill_counted(p, t, 512, last))(
            params, jnp.asarray(prompts), jnp.asarray(lens - 1))
    assert counts.tolist() == [int(lens.sum()) * 6, len(lanes) * 512 * 6]
    np.testing.assert_allclose(np.asarray(logits), ref[lens - 1], atol=3e-4)
    # the slab: each row's state and tail at ITS OWN last token
    for l in range(6):
        np.testing.assert_allclose(
            np.asarray(slab["state"][0, :, l]), ref_states[l][:len(lanes)],
            atol=1e-4)
        for i, n in enumerate(lens):
            want = np.zeros((3, 128), np.float32)
            want[max(0, 3 - n):] = ref_a[l][max(0, n - 3):n]
            np.testing.assert_allclose(
                np.asarray(slab["conv"][0, i, l]), want, atol=1e-4)
    for l in range(2):
        for i, n in enumerate(lens):
            np.testing.assert_allclose(
                np.asarray(slab["k"][l, i, 0, :n]), ref_kv[l][0][:n, 0], atol=1e-4)
    # into lanes of a cache that holds another occupant's leftovers
    cache = jax.tree_util.tree_map(lambda a: a + 7, model.cache_layers(LANES, 512))
    before = jax.tree_util.tree_map(np.asarray, cache)
    for i, lane in enumerate(lanes):
        for name in cache:
            for l, layer in enumerate(cache[name]):
                cache[name][l] = jax.lax.dynamic_update_slice(
                    layer, slab[name][l, i:i + 1],
                    (lane,) + (0,) * (layer.ndim - 1))
    step = jax.jit(model.decode_step_cache)
    live = np.zeros(LANES, bool)
    live[lanes] = True
    at = np.zeros(LANES, np.int64)
    at[lanes] = lens
    for s in range(STEPS):
        pos = np.where(live, at + s, 0)
        out, cache, counts = step(
            params, cache,
            jnp.asarray(np.where(live, tokens[pos], 0)[:, None], jnp.int32),
            jnp.asarray(pos, jnp.int32),
            lens=jnp.asarray(np.where(live, pos + 1, 0), jnp.int32))
        np.testing.assert_allclose(
            np.asarray(out)[lanes], ref[ends[:, s]], atol=5e-4)
        # off a TPU the read is the dots': the bound of every lane
        assert counts.tolist() == [
            len(lanes) * 6, 6, LANES * 512 * 2, int((lens + s + 1).sum()) * 2]
    for l in range(6):
        np.testing.assert_allclose(
            np.asarray(cache["state"][0])[lanes, l],
            ref_states[l][len(lanes):], atol=1e-4)
        for i, lane in enumerate(lanes):
            n = lens[i] + STEPS
            np.testing.assert_allclose(
                np.asarray(cache["conv"][0])[lane, l], ref_a[l][n - 3:n],
                atol=1e-4)
    for l in range(2):
        for i, lane in enumerate(lanes):
            n = lens[i] + STEPS
            np.testing.assert_allclose(
                np.asarray(cache["v"][l])[lane, 0, :n], ref_kv[l][1][:n, 0],
                atol=1e-4)
    # an idle lane is untouched in every kind that has no position to park at
    idle = [j for j in range(LANES) if j not in LENGTHS]
    for name in ("conv", "state"):
        assert np.array_equal(np.asarray(cache[name][0])[idle],
                              before[name][0][idle])


def test_each_wrong_reference_is_told_from_the_served_model(served):
    """The controls of the chip's comparison, at this size: every one moves
    the logits or the state by far more than the served model differs."""
    model, params = served
    tokens = np.random.default_rng(2).integers(0, 256, size=48)
    at = list(range(48))
    want, _, _, states = reference.forward(params, model.cfg, tokens, at,
                                           state_at=[47])
    got = np.asarray(model.apply(params, jnp.asarray(tokens)[None])[0])
    scale = want.std()
    assert np.abs(got - want).max() / scale < 1e-3
    for variant in reference.VARIANTS:
        wrong, _, _, wrong_states = reference.forward(
            params, model.cfg, tokens, at, variant, state_at=[47])
        moved = np.abs(wrong - want).max() / scale
        state = max(np.linalg.norm(w - s) / np.linalg.norm(s)
                    for w, s in zip(wrong_states, states))
        assert not np.isfinite(moved) or moved > 0.05 or state > 1e-3, variant
    with pytest.raises(ValueError, match="unknown variant"):
        reference.forward(params, model.cfg, tokens, at, "no_such")


@pytest.mark.parametrize("setting", [
    {"prefix_cache_hbm_bytes": 1 << 20}, {"prefill_chunk": 64},
    {"hbm_ledger_bytes": 1 << 30}, {"host_kv_tier_bytes": 1 << 20},
    {"swap_drain_ms": 100}])
def test_what_needs_the_state_carried_is_refused_at_load(served, setting):
    model, params = served
    with pytest.raises(UnsupportedByModel):
        ContinuousBatcher(model, params, slots=2, max_seq=512, **setting)


def test_refusals_name_their_reason_and_requests_are_refused_where_they_come_in(
        served):
    model, params = served
    assert set(model.serving_refuses) == {
        "speculation", "mesh", "kv_tier", "prefix_cache", "chunked_prefill",
        "preemption", "migration"}
    for feature in model.serving_refuses:
        with pytest.raises(UnsupportedByModel, match=feature):
            model.check_serves(**{feature: True})
    model.check_serves(**{f: False for f in model.serving_refuses})
    batcher = ContinuousBatcher(model, params, slots=2, max_seq=512)
    try:
        with pytest.raises(UnsupportedByModel):
            batcher.submit_checkpoint({"prompt": [1, 2, 3], "emitted": [4]})
        with pytest.raises(UnsupportedByModel):
            batcher.export_prefill([1, 2, 3])
        with pytest.raises(UnsupportedByModel):
            batcher.admit_remote({"tokens": [1, 2, 3]}, {})
    finally:
        batcher.close()
    for call in (lambda: model.decode_step_ragged_list(params, [], [], None, None),
                 lambda: model.prefill_chunk(params, None, None, 0, 0),
                 lambda: model.prefill_with_prefix(params, None, None, 0),
                 lambda: model.decode_chunk_ragged_list(params, [], [], None, None),
                 lambda: model.decode_block_cache(params, {}, None, None),
                 lambda: model.loss_fn(params, None),
                 lambda: model.param_sharding(None, params)):
        with pytest.raises(UnsupportedByModel, match="jamba block"):
            call()
