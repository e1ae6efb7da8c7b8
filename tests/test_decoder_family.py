"""The decoder families' one interface (``models/family.py``): the registry
``DecoderLM(block=...)`` looks a block up in, the typed refusals defined once
on the base, and the one expert-layer glue (``ops.experts.routed_ffn``)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from seldon_core_tpu.models import family as families
from seldon_core_tpu.models.family import DecoderFamily, UnsupportedByModel
from seldon_core_tpu.models.llm import DecoderLM, LLMConfig
from seldon_core_tpu.ops import experts

SMALL = {
    "llama": dict(vocab_size=64, d_model=64, n_layers=2, n_heads=4,
                  n_kv_heads=2, d_ff=128, max_seq=128),
    "afmoe": dict(vocab_size=64, d_model=64, n_layers=2, n_heads=4,
                  n_kv_heads=2, d_ff=128, max_seq=128,
                  layer_types=("sliding_attention", "full_attention"),
                  sliding_window=32, n_dense_layers=1, n_routed_experts=4,
                  experts_per_tok=2, expert_width=32, n_shared_experts=1),
    "qwen3_next": dict(vocab_size=64, d_model=64, n_layers=2, n_heads=4,
                       n_kv_heads=2, head_dim=32, d_ff=128, max_seq=128,
                       layer_types=("linear_attention", "full_attention"),
                       linear_key_heads=2, linear_value_heads=4,
                       linear_key_dim=16, linear_value_dim=16,
                       linear_conv_kernel=4, partial_rotary_factor=0.25,
                       n_routed_experts=4, experts_per_tok=2, expert_width=32,
                       shared_expert_width=32),
    "joyai_llm_flash": dict(vocab_size=64, d_model=64, n_layers=2, n_heads=4,
                            n_kv_heads=4, d_ff=128, max_seq=128,
                            q_lora_rank=32, kv_lora_rank=128,
                            qk_nope_head_dim=16, qk_rope_head_dim=16,
                            v_head_dim=16, n_dense_layers=1,
                            n_routed_experts=4, experts_per_tok=2,
                            expert_width=32, n_shared_experts=1),
    "evabyte": dict(vocab_size=64, d_model=64, n_layers=2, n_heads=4,
                    n_kv_heads=4, d_ff=128, max_seq=128, window_size=32,
                    chunk_size=8, num_pred_heads=2, norm_add_unit_offset=True,
                    fp32_skip_add=True),
    "sdar_moe": dict(vocab_size=64, d_model=64, n_layers=2, n_heads=4,
                     n_kv_heads=2, head_dim=16, max_seq=128,
                     n_routed_experts=4, experts_per_tok=2, expert_width=32,
                     denoising_steps=2, mask_token_id=63),
    "lfm2_moe": dict(vocab_size=64, d_model=64, n_layers=2, n_heads=4,
                     n_kv_heads=2, head_dim=16, d_ff=128, max_seq=128,
                     layer_types=("conv", "full_attention"), conv_kernel=3,
                     n_dense_layers=1, n_routed_experts=4, experts_per_tok=2,
                     expert_width=32),
    "jamba": dict(vocab_size=64, d_model=64, n_layers=2, n_heads=4,
                  n_kv_heads=1, head_dim=16, d_ff=128, max_seq=128,
                  attn_layer_period=2, attn_layer_offset=1, mamba_d_state=16,
                  mamba_d_conv=4, mamba_dt_rank=4, mamba_expand=2),
    "mimo_v2": dict(vocab_size=64, d_model=64, n_layers=2, n_heads=4,
                    n_kv_heads=1, head_dim=24, d_ff=128, max_seq=128,
                    layer_types=("full_attention", "sliding_attention"),
                    v_head_width=16, rotary_dim=8, swa_window=16,
                    swa_n_kv_heads=2, n_dense_layers=1, n_routed_experts=4,
                    experts_per_tok=2, expert_width=32),
}
# a field that is one family's own, for every OTHER block an unknown keyword
OWN_FIELD = {
    "afmoe": "sliding_window", "qwen3_next": "linear_conv_kernel",
    "joyai_llm_flash": "kv_lora_rank", "evabyte": "window_size",
    "sdar_moe": "block_length", "lfm2_moe": "conv_kernel",
    "jamba": "mamba_d_state", "mimo_v2": "swa_window",
}
# the optional paths and the ``serving_refuses`` feature that guards each
GUARDED = {
    "decode_chunk_ragged_list": "speculation",
    "prefill_chunk": "chunked_prefill",
    "prefill_with_prefix": "prefix_cache",
    "param_sharding": "mesh", "set_serving_mesh": "mesh",
    "cache_sharding": "mesh", "slab_sharding": "mesh",
}
LLAMA_OWN = ("decode_step_ragged_list", "backbone", "loss_fn", "_decode",
             "decode_step", "decode_step_ragged", "generate")


def test_the_registry_names_the_nine_blocks():
    assert sorted(SMALL) == sorted(families.FAMILIES)


@pytest.mark.parametrize("block", sorted(SMALL))
def test_a_step_is_one_token_a_lane_but_where_a_family_generates_by_blocks(block):
    """``block_tokens()`` is what the scheduler asks; the pass over a block
    and how it fills in are defined once as a typed refusal and served by
    the family that generates so."""
    model = DecoderLM(block=block, **SMALL[block])
    by_blocks = block == "sdar_moe"
    assert model.block_tokens() == (4 if by_blocks else 1)
    for path in ("decode_block_cache", "block_unmask"):
        inherited = getattr(type(model), path) is getattr(DecoderFamily, path)
        assert inherited != by_blocks, path
        if inherited:
            with pytest.raises(UnsupportedByModel,
                               match=f"the {block} block has no pass over a block"):
                getattr(model, path)()
    assert ("block_forwards" in model.step_counter_names) == by_blocks


@pytest.mark.parametrize("block", sorted(SMALL))
def test_decoderlm_builds_the_registered_class_fully_initialised(block):
    registered = families.family_class(block)
    assert f"{registered.__module__}.{registered.__name__}" == (
        families.FAMILIES[block])
    model = DecoderLM(block=block, seed=7, **SMALL[block])
    assert type(model) is registered and isinstance(model, DecoderFamily)
    assert (DecoderLM in type(model).__mro__) == (block == "llama")
    # __init__ has run, on the family's own configuration
    assert type(model.cfg) is registered.config_class
    assert issubclass(registered.config_class, LLMConfig)
    assert model.cfg.block == block and model.cfg.d_model == 64
    assert model._extra == {"seed": 7}
    assert model.compute_dtype == model.cfg.dtype
    assert registered(**SMALL[block]).cfg.block == block
    # a field of another family's own is any unknown keyword here
    for other, name in OWN_FIELD.items():
        if other == block:
            assert name in {f.name for f in dataclasses.fields(model.cfg)}
            continue
        assert name not in {f.name for f in dataclasses.fields(model.cfg)}
        stray = DecoderLM(block=block, **{**SMALL[block], name: 5})
        assert stray._extra == {name: 5}
    with pytest.raises(ValueError, match="unknown block variant 'mamba'"):
        DecoderLM(block="mamba", **SMALL[block])


def test_the_shared_config_holds_no_familys_own_field():
    shared = {f.name for f in dataclasses.fields(LLMConfig)}
    assert not shared & set(OWN_FIELD.values())
    assert {"block", "head_dim", "layer_types", "n_dense_layers",
            "n_routed_experts", "experts_per_tok", "expert_width",
            "n_shared_experts", "route_scale", "experts_held"} <= shared


@pytest.mark.parametrize("block", sorted(SMALL))
def test_config_differs_compares_like_with_like(block):
    """What the server's hot-swap and tenant checks ask: the fields in which
    another model's architecture differs, ``residual_scale`` aside."""
    model = DecoderLM(block=block, **SMALL[block])
    same = DecoderLM(block=block, residual_scale=0.25, **SMALL[block])
    assert model.config_differs(same) == [] == same.config_differs(model)
    deeper = DecoderLM(block=block, **{**SMALL[block], "vocab_size": 128})
    assert model.config_differs(deeper) == ["vocab_size"]
    other = "llama" if block != "llama" else "evabyte"
    differs = model.config_differs(DecoderLM(block=other, **SMALL[other]))
    assert "block" in differs
    assert OWN_FIELD[block if block != "llama" else other] in differs


@pytest.mark.parametrize("block", sorted(SMALL))
def test_an_optional_path_is_served_or_refused_typed_with_the_reason(block):
    model = DecoderLM(block=block, **SMALL[block])
    refused = 0
    for path, feature in GUARDED.items():
        inherited = getattr(type(model), path) is getattr(DecoderFamily, path)
        if feature not in model.serving_refuses:
            assert not inherited, (path, "neither served nor refused")
            continue
        if not inherited:
            continue       # refuses the feature for another reason (afmoe)
        with pytest.raises(UnsupportedByModel) as e:
            getattr(model, path)()
        assert f"the {block} block" in str(e.value)
        assert model.serving_refuses[feature] in str(e.value)
        refused += 1
    for path in LLAMA_OWN:
        if getattr(type(model), path) is getattr(DecoderFamily, path):
            with pytest.raises(UnsupportedByModel, match=f"{block} block"):
                getattr(model, path)()
            refused += 1
    if block == "llama":
        # the llama block serves every path: nothing of the base's is left
        assert refused == 0 and model.serving_refuses == {}
        mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("model",))
        model.set_serving_mesh(mesh)
        params = jax.eval_shape(model.init_params, 0)
        assert jax.tree_util.tree_leaves(model.param_sharding(mesh, params))
        model.cache_sharding(mesh), model.slab_sharding(mesh)
    else:
        assert refused >= 6 + 4


# -- the one expert-layer glue ---------------------------------------------------

N, D, E, F, K = 24, 64, 8, 32, 2


@pytest.fixture(scope="module")
def layer():
    keys = jax.random.split(jax.random.PRNGKey(0), 6)
    rows = jax.random.normal(keys[0], (N, D), jnp.float32)
    router = jax.random.normal(keys[1], (D, E), jnp.float32) / np.sqrt(D)
    bias = jax.random.normal(keys[2], (E,), jnp.float32) * 0.1
    stacks = (jax.random.normal(keys[3], (E, D, F), jnp.float32) / np.sqrt(D),
              jax.random.normal(keys[4], (E, D, F), jnp.float32) / np.sqrt(D),
              jax.random.normal(keys[5], (E, F, D), jnp.float32) / np.sqrt(F))
    real = jnp.arange(N) % 6 != 5         # every sixth row is padding
    live = jnp.arange(N) % 4 != 3         # every fourth lane idles
    return rows, router, bias, stacks, real, live


# the three families' call patterns: (score, bias?, scale, held, redirect)
PATTERNS = {
    "afmoe": ("sigmoid", True, 2.5, None, True),
    "qwen3_next": ("softmax", False, 1.0, (2, 4), True),
    "qwen3_next_all_held": ("softmax", False, 1.0, None, False),
    "joyai_llm_flash": ("sigmoid", True, 2.0, (0, 4), True),
}


@pytest.mark.parametrize("pattern", sorted(PATTERNS))
def test_routed_ffn_is_route_and_the_experts_called_by_hand(layer, pattern):
    rows, router, bias, stacks, real, live = layer
    score, biased, scale, held, redirect = PATTERNS[pattern]
    bias = bias if biased else None
    if held is not None:
        stacks = tuple(w[held[0]:held[0] + held[1]] for w in stacks)
    how = dict(held=held, n_routed=E, mesh=None, redirect_pads=redirect)
    picks, weights = experts.route(rows, router, bias, K, scale, score=score)

    # a prefill: the grouped experts, pad rows' picks sent to no expert or not
    y, got_picks, counts = experts.routed_ffn(
        rows, router, bias, K, scale, score, stacks, live=None, real=real, **how)
    sent = jnp.where(real[:, None], picks, E) if redirect else picks
    want, want_counts = experts.grouped_experts(
        rows, sent, weights, *stacks, held=held, n_routed=E)
    np.testing.assert_array_equal(got_picks, picks)
    np.testing.assert_array_equal(y, want)
    np.testing.assert_array_equal(counts, want_counts)
    assert counts.shape == (len(experts.GROUPED_COUNTS),)
    pads = np.asarray(~real)
    assert (np.asarray(y)[pads] == 0).all() == redirect
    # no ``real``: nothing to redirect, every row goes where it picked
    y_all, _, _ = experts.routed_ffn(
        rows, router, bias, K, scale, score, stacks, live=None, real=None, **how)
    np.testing.assert_array_equal(
        y_all, experts.grouped_experts(rows, picks, weights, *stacks,
                                       held=held, n_routed=E)[0])
    np.testing.assert_array_equal(np.asarray(y_all)[~pads], np.asarray(y)[~pads])

    # a decode step: the touched-only read and its three counts
    y, got_picks, (touched, routed, here) = experts.routed_ffn(
        rows, router, bias, K, scale, score, stacks, live=live, real=None, **how)
    want, want_touched, want_routed = experts.decode_experts(
        rows, picks, weights, live, *stacks, held=held)
    np.testing.assert_array_equal(got_picks, picks)
    np.testing.assert_array_equal(y, want)
    assert (int(touched), int(routed)) == (int(want_touched), int(want_routed))
    assert int(routed) == int(live.sum()) * K
    lo, n = held or (0, E)
    landed = (np.asarray(picks) >= lo) & (np.asarray(picks) < lo + n)
    assert int(here) == int((landed & np.asarray(live)[:, None]).sum())
    if held is None:
        assert int(here) == int(routed)
    assert (np.asarray(y)[~np.asarray(live)] == 0).all()
