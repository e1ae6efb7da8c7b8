"""The sdar_moe family (``models/sdar_moe.py``): prefill under the block mask
and then passes through the cache against the plain reference's full
forward over a canvas (``benchmark/reference/sdar_moe.py``): logits, not
tokens, at every state of a block, for the first generated block that
holds a prompt's tail, for lanes of different lengths with idle ones among
them, and the committed rows themselves; both remasking rules against the
reference's; the seeded draw; the typed refusals. Seeded random weights at
a small size, float32, on the CPU."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import sdar_moe as reference
from seldon_core_tpu.models.family import FAMILIES, UnsupportedByModel
from seldon_core_tpu.models.llm import DecoderLM
from seldon_core_tpu.models.sdar_moe import DYNAMIC, STATIC, SdarMoeLM
from seldon_core_tpu.serving.continuous import ContinuousBatcher

MASK = 96
SMALL = dict(vocab_size=97, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
             head_dim=16, max_seq=256, n_routed_experts=8, experts_per_tok=2,
             expert_width=32, dtype="float32", denoising_steps=2,
             mask_token_id=MASK, rope_theta=1e6, norm_eps=1e-6)
W = 4


@pytest.fixture(scope="module")
def tiny():
    model = DecoderLM(block="sdar_moe", **SMALL)
    assert type(model) is SdarMoeLM and model.block_tokens() == W
    return model, model.init_params(3)


def _lay(model, params, lanes, prompts, cache_len=256):
    """A cache of ``lanes`` lanes, lane j holding prompts[j] (None: idle)
    through the family's own prefill: ``(cache, base [lanes])``."""
    cache = model.cache_layers(lanes, cache_len)
    base = np.zeros(lanes, np.int32)
    for j, prompt in enumerate(prompts):
        if prompt is None:
            continue
        n = len(prompt)
        bucket = -(-n // 16) * 16
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :n] = prompt
        _logits, rows = model.prefill(params, jnp.asarray(padded), bucket,
                                      jnp.asarray([n - 1]))
        for kind in ("k", "v"):
            for l in range(model.cfg.n_layers):
                cache[kind][l] = cache[kind][l].at[j, :, :bucket].set(
                    rows[kind][l, 0])
        base[j] = n - n % W
    return cache, base


def _states(tail, final):
    """The states a block goes through: ``(tokens [W], masked [W])``, all
    masked but a prompt's tail, partly, none (the commit)."""
    part = min(W, tail + 2)
    out = []
    for filled in sorted({tail, part, W}):
        masked = np.arange(W) >= filled
        out.append((np.where(masked, MASK, final), masked))
    return out


@pytest.mark.parametrize("remainder", [0, 1, 2, 3])
def test_prefill_then_passes_are_the_references_forward_at_every_state(
        tiny, remainder):
    """The first generated block, which holds the prompt's last ``remainder``
    tokens, through all its states and then the block after it (which reads
    the rows the commit left): each pass's logits are the reference's over
    the canvas up to the block's end."""
    model, params = tiny
    rng = np.random.default_rng(remainder)
    n = 20 + remainder
    prompt = rng.integers(0, MASK, size=n)
    final = np.concatenate([prompt, rng.integers(0, MASK, size=2 * W)])
    cache, base = _lay(model, params, 1, [prompt])
    assert base[0] == 20
    step = jax.jit(model.decode_block_cache)
    b = int(base[0])
    states = [(b, s) for s in _states(remainder, final[b:b + W])]
    states += [(b + W, s) for s in _states(0, final[b + W:b + 2 * W])]
    for at, (tokens, masked) in states:
        logits, cache, counts = step(
            params, cache, jnp.asarray(tokens[None]), jnp.asarray([at]),
            jnp.asarray(masked[None]))
        canvas = np.concatenate([final[:at], tokens])
        want = reference.forward(params, model.cfg, canvas,
                                 list(range(at, at + W)))[0]
        np.testing.assert_allclose(np.asarray(logits[0]), want, atol=2e-4)
        named = dict(zip(model.step_counter_names, np.asarray(counts)))
        assert named["block_forwards"] == 1
        assert named["block_commit_forwards"] == int(not masked.any())
        assert named["moe_rows_routed"] == W * 2 * 2


def test_the_commit_leaves_the_references_rows_and_a_denoising_pass_does_not(tiny):
    model, params = tiny
    rng = np.random.default_rng(7)
    final = rng.integers(0, MASK, size=24 + W)
    cache, _ = _lay(model, params, 1, [final[:24]])
    rows = reference.forward(params, model.cfg, final, [0])[3]
    step = jax.jit(model.decode_block_cache)
    states = _states(0, final[24:])
    (half, half_masked), (done, none_masked) = states[1], states[-1]
    for tokens, masked, agrees in ((half, half_masked, False),
                                   (done, none_masked, True)):
        _l, cache, _c = step(params, cache, jnp.asarray(tokens[None]),
                             jnp.asarray([24]), jnp.asarray(masked[None]))
        for l in range(model.cfg.n_layers):
            for kind, ref in zip(("k", "v"), rows[l]):
                got = np.asarray(cache[kind][l][0, :, :24 + W]).transpose(1, 0, 2)
                close = np.allclose(got, np.asarray(ref), atol=2e-4)
                # layer 0's values see their own position's token alone
                if agrees:
                    assert close, (l, kind)
                elif l > 0:
                    assert not close, (l, kind)


def test_lanes_of_their_own_lengths_with_idle_ones_among_them(tiny):
    """Lengths either side of the kernel's block edge at 128, every
    remainder, idle lanes between them: each live lane's logits are the
    reference's over its own canvas, and an idle lane's rows stay as they
    were."""
    model, params = tiny
    rng = np.random.default_rng(11)
    text = rng.integers(0, MASK, size=140)
    lens = [126, None, 127, 128, None, 129, 130, 3]
    prompts = [None if n is None else text[:n] for n in lens]
    cache, base = _lay(model, params, len(lens), prompts)
    live = np.array([n is not None for n in lens])
    tokens = np.full((len(lens), W), MASK, np.int32)
    masked = np.ones((len(lens), W), bool)
    for j, n in enumerate(lens):
        if n is not None:
            tokens[j, :n % W] = text[base[j]:n]
            masked[j, :n % W] = False
    idle_before = [np.asarray(cache["k"][l][1]) for l in range(2)]
    logits, cache, counts = jax.jit(model.decode_block_cache)(
        params, cache, jnp.asarray(tokens), jnp.asarray(base),
        jnp.asarray(masked), None, jnp.asarray(np.where(live, base + W, 0)))
    for j, n in enumerate(lens):
        if n is None:
            continue
        canvas = np.concatenate([text[:base[j]], tokens[j]])
        want = reference.forward(params, model.cfg, canvas,
                                 list(range(base[j], base[j] + W)))[0]
        np.testing.assert_allclose(np.asarray(logits[j]), want, atol=3e-4)
    for l in range(2):
        np.testing.assert_array_equal(np.asarray(cache["k"][l][1]), idle_before[l])
    named = dict(zip(model.step_counter_names, np.asarray(counts)))
    assert named["block_forwards"] == 6 and named["block_commit_forwards"] == 0
    # a lane's length rounded up to the kernel's block (256 over this
    # model's 2 KV heads of 16 in this cache of 256), two layers; and the
    # lengths alone
    assert named["block_rows_read"] == 2 * 6 * 256
    assert named["block_rows_live"] == 2 * sum(
        n - n % W + W for n in lens if n is not None)
    assert named["moe_rows_routed"] == 6 * W * 2 * 2
    assert named["moe_layer_steps"] == 2


@pytest.mark.parametrize("shape,cache_len,block", [
    # 2 KV heads of 16 in float32: 128 keys of K and V are 32 KiB, far
    # under what covers the chain, so the block is the largest up to 1,024
    # that divides the cache, at 8 query rows a KV head as at 32
    (dict(n_heads=4), 1024, 1024),
    (dict(n_heads=16), 1024, 1024),
    (dict(n_heads=16), 1536, 512),
    (dict(n_heads=16), 768, 256),
    (dict(n_heads=16), 640, 128),     # which 256 does not divide
    # 4 KV heads of 128 in float32: 512 KiB, a copy that covers the chain
    (dict(n_heads=4, n_kv_heads=4, head_dim=128), 768, 128),
])
def test_the_rows_read_round_to_the_block_the_kernel_walks(shape, cache_len, block):
    """``block_rows_read`` is what the kernel streams (each live lane's
    length rounded up to ``walk_block``'s answer for the pass's shapes) and
    ``block_rows_live`` what the lengths hold, both over the layers; an
    idle lane adds to neither."""
    from seldon_core_tpu.ops.decode_attention import walk_block

    model = DecoderLM(block="sdar_moe", **dict(SMALL, **shape))
    cfg = model.cfg
    assert walk_block(cfg.n_kv_heads, cfg.head_dim, cfg.dtype, cache_len) == block
    params = model.init_params(5)
    base = np.array([0, 124, 128, 252, 256, 508, 512, 600], np.int32)
    live = np.array([1, 1, 1, 1, 0, 1, 1, 1], bool)
    lens = np.where(live, base + W, 0)
    cache = model.cache_layers(len(base), cache_len)
    _logits, _cache, counts = jax.jit(model.decode_block_cache)(
        params, cache, jnp.full((len(base), W), MASK, jnp.int32),
        jnp.asarray(base), jnp.ones((len(base), W), bool), None,
        jnp.asarray(lens))
    named = dict(zip(model.step_counter_names, np.asarray(counts)))
    assert named["block_rows_read"] == 2 * int((-(-lens // block) * block).sum())
    assert named["block_rows_live"] == 2 * int(lens.sum())
    assert named["block_forwards"] == live.sum()


def _random_pass(rng, lanes=6, vocab=97):
    logits = rng.standard_normal((lanes, W, vocab)).astype(np.float32) * 3
    masked = rng.random((lanes, W)) < 0.7
    masked[0] = True
    masked[1] = False                   # a commit: nothing to fill in
    tokens = np.where(masked, MASK, rng.integers(0, MASK, size=(lanes, W)))
    return logits, tokens.astype(np.int32), masked


@pytest.mark.parametrize("remasking,steps", [
    (STATIC, 2), (STATIC, 4), (STATIC, 3), (DYNAMIC, 4), (DYNAMIC, 2)])
def test_a_pass_fills_in_what_the_references_rule_does(remasking, steps):
    """Both rules, logits made to cross the threshold for the dynamic one
    (a position's winner raised until its probability passes 0.9), ties
    included, at every pass number."""
    model = SdarMoeLM(**{**SMALL, "remasking": remasking,
                         "denoising_steps": steps})
    rng = np.random.default_rng(steps)
    for n_pass in range(steps):
        logits, tokens, masked = _random_pass(rng)
        logits[2, 1, 5] = logits[2, 3, 9] = 40.0      # over the threshold
        logits[3] = logits[3, 0]                      # ties: lower position
        alive = np.array([True, True, True, True, False, True])
        new_tok, new_masked, _keys, counts = model.block_unmask(
            jnp.asarray(logits), jnp.asarray(tokens), jnp.asarray(masked),
            jnp.full((6,), n_pass, jnp.int32), jnp.asarray(alive),
            jnp.zeros((6,), jnp.float32),
            jax.vmap(jax.random.PRNGKey)(jnp.arange(6)), False)
        taken = 0
        for j in range(6):
            x0, take = reference.unmask(logits[j], masked[j], n_pass, model.cfg)
            if not alive[j]:
                take[:] = False
            want = np.where(take, x0, tokens[j])
            np.testing.assert_array_equal(np.asarray(new_tok[j]), want)
            np.testing.assert_array_equal(np.asarray(new_masked[j]),
                                          masked[j] & ~take)
            taken += int(take.sum())
        assert int(counts[model.step_counter_names.index(
            "block_tokens_unmasked")]) == taken
        assert MASK not in np.asarray(new_tok)[np.asarray(~new_masked) & masked]


def test_the_dynamic_rule_takes_every_position_over_the_threshold():
    model = SdarMoeLM(**{**SMALL, "remasking": DYNAMIC, "denoising_steps": 4})
    logits = np.zeros((1, W, 97), np.float32)
    logits[0, 1, 5] = logits[0, 3, 9] = logits[0, 2, 7] = 40.0
    masked = np.array([[True, True, False, True]])
    tokens = np.array([[MASK, MASK, 11, MASK]], np.int32)
    new_tok, new_masked, _k, _c = model.block_unmask(
        jnp.asarray(logits), jnp.asarray(tokens), jnp.asarray(masked),
        jnp.zeros((1,), jnp.int32), jnp.ones((1,), bool),
        jnp.zeros((1,), jnp.float32), jax.random.PRNGKey(0)[None], False)
    # the static share is one position; two lie over 0.9 and both go
    assert np.asarray(new_tok).tolist() == [[MASK, 5, 11, 9]]
    assert np.asarray(new_masked).tolist() == [[True, False, False, False]]


def test_the_mask_is_never_emitted_and_a_draw_is_its_seeds(tiny):
    model, _params = tiny
    rng = np.random.default_rng(5)
    logits, tokens, masked = _random_pass(rng)
    logits[:, :, MASK] = 50.0                 # the mask's own logit wins
    args = (jnp.asarray(logits), jnp.asarray(tokens), jnp.asarray(masked),
            jnp.zeros((6,), jnp.int32), jnp.ones((6,), bool))
    keys = jax.vmap(jax.random.PRNGKey)(jnp.arange(6))
    temps = jnp.asarray([0.0, 0.0, 1.0, 1.0, 0.7, 0.0])
    a = model.block_unmask(*args, temps, keys, True)
    b = model.block_unmask(*args, temps, keys, True)
    c = model.block_unmask(*args, temps, keys + 1, True)
    np.testing.assert_array_equal(np.asarray(a[0]), np.asarray(b[0]))
    assert not np.array_equal(np.asarray(a[0])[2:5], np.asarray(c[0])[2:5])
    filled = np.asarray(~a[1]) & masked
    assert filled.any() and MASK not in np.asarray(a[0])[filled]
    # a greedy lane's tokens are the same with and without the draw compiled
    greedy = model.block_unmask(*args, jnp.zeros((6,)), keys, False)
    for j in (0, 1, 5):
        np.testing.assert_array_equal(np.asarray(a[0][j]), np.asarray(greedy[0][j]))


def test_a_prompt_may_hold_the_masks_id(tiny):
    """Masked positions are the lane's mask bits: a prompt token that IS the
    mask's id is a token (embedded as such, never filled in)."""
    model, params = tiny
    prompt = [5, MASK, 7, 9, 11, MASK]
    got = _generate(model, params, prompt, 6)
    assert got == reference.generate(params, model.cfg, prompt, 6)
    assert MASK not in got


def _generate(model, params, prompt, new, **kw):
    ContinuousBatcher.MIN_ATTN_BUCKET, keep = 16, ContinuousBatcher.MIN_ATTN_BUCKET
    try:
        b = ContinuousBatcher(model, params, slots=2, max_seq=256,
                              prefill_buckets=(16, 32), steps_per_poll=4,
                              attn_bucket=16, **kw)
        b.start()
        try:
            return b.submit(list(prompt), max_new_tokens=new).result(
                timeout=300)[len(prompt):]
        finally:
            b.close()
    finally:
        ContinuousBatcher.MIN_ATTN_BUCKET = keep


@pytest.mark.parametrize("feature", sorted(SdarMoeLM.serving_refuses))
def test_what_it_does_not_serve_is_refused_typed_at_load(tiny, feature):
    model, params = tiny
    with pytest.raises(UnsupportedByModel, match="SdarMoeLM does not serve"):
        model.check_serves(**{feature: True})
    settings = {
        "speculation": dict(draft_model=model, draft_params=params),
        "fused": dict(fused_steps_per_dispatch=8),
        "chunked_prefill": dict(prefill_chunk=16),
        "prefix_cache": dict(prefix_cache_hbm_bytes=1 << 20),
        "kv_tier": dict(host_kv_tier_bytes=1 << 20),
        "preemption": dict(hbm_ledger_bytes=1 << 30),
    }
    if feature in settings:
        with pytest.raises(UnsupportedByModel, match=feature):
            ContinuousBatcher(model, params, slots=2, max_seq=64,
                              **settings[feature])


def test_the_configuration_is_held_to_what_the_kernel_and_the_rule_take():
    for wrong, why in ((dict(block_length=3), "power of two"),
                       (dict(block_length=16), "power of two"),
                       (dict(max_seq=250), "divides max_seq"),
                       (dict(denoising_steps=5), "passes fill a block"),
                       (dict(remasking="random"), "remasking"),
                       (dict(mask_token_id=97), "inside the vocabulary"),
                       (dict(n_routed_experts=0), "expert layer")):
        with pytest.raises(ValueError, match=why):
            SdarMoeLM(**{**SMALL, **wrong})
    fields = {f.name for f in dataclasses.fields(SdarMoeLM.config_class)}
    assert {"block_length", "denoising_steps", "remasking",
            "confidence_threshold", "mask_token_id"} <= fields
    assert FAMILIES["sdar_moe"].endswith("sdar_moe.SdarMoeLM")
    with pytest.raises(ValueError, match="no multiple of the family's block"):
        ContinuousBatcher(SdarMoeLM(**SMALL), {}, slots=1, max_seq=130)
