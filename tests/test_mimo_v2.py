"""The mimo_v2 family (``models/mimo_v2.py``) against its plain reference
(``benchmark/reference/mimo_v2.py``) at a small size with every ratio kept:
2 full and 5 window layers in the served order, 1 and 2 KV heads for 4 and
8, keys of 24 beside values of 16 with 8 rotary dims, a window of 16, 4 of
32 experts held, top 4. Float32 on the CPU: prefill then decode through the
cache of kinds IS the reference's one forward, across every multiple of the
window a lane crosses; each wrong model is told apart; the shares add up to
the uncut layer."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import mimo_v2 as reference
from seldon_core_tpu.models.family import UnsupportedByModel
from seldon_core_tpu.models.llm import DecoderLM
from seldon_core_tpu.models.mimo_v2 import MimoV2LM

KINDS = (["full_attention"] + ["sliding_attention"] * 4
         + ["full_attention", "sliding_attention"])
SMALL = dict(
    block="mimo_v2", vocab_size=96, d_model=64, n_layers=7, n_heads=8,
    n_kv_heads=1, head_dim=24, d_ff=128, max_seq=96, layer_types=KINDS,
    rope_theta=1e7, swa_rope_theta=1e4,
    v_head_width=16, rotary_dim=8, swa_window=16, swa_n_kv_heads=2,
    n_dense_layers=1, n_routed_experts=32, experts_per_tok=4, expert_width=32,
    experts_held=(4, 4), dtype="float32", residual_scale=0.5)
W = 16
LANES = 6
# a float32 served path against a float32 reference: the largest sound
# reading is 2e-6 of a logit's deviation; the mildest control reads 4e-3
TIGHT = 1e-4


@pytest.fixture(scope="module")
def served():
    model = DecoderLM(**SMALL)
    assert type(model) is MimoV2LM
    return model, model.init_params(3)


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(0).integers(0, 96, size=96)


@pytest.fixture(scope="module")
def full(served, tokens):
    model, params = served
    return reference.forward(params, model.cfg, tokens, list(range(len(tokens))))


def _filled(model, params, tokens, lens, bucket, lanes):
    """A cache of ``LANES`` lanes with the prompts' first ``lens`` tokens
    prefilled together in ``bucket`` and laid into ``lanes`` as the
    batcher's insert lays them: each kind's rows from 0 along every axis."""
    prompt = np.zeros((len(lens), bucket), np.int32)
    for i, n in enumerate(lens):
        prompt[i, :n] = tokens[:n]
    logits, slab = model.prefill(
        params, jnp.asarray(prompt), bucket,
        last_index=jnp.asarray([n - 1 for n in lens], jnp.int32))
    cache = model.init_cache(LANES)
    for name, layers in cache.items():
        for l in range(len(layers)):
            for i, lane in enumerate(lanes):
                layers[l] = jax.lax.dynamic_update_slice(
                    layers[l], slab[name][l, i:i + 1],
                    (lane,) + (0,) * (layers[l].ndim - 1))
    return np.asarray(logits), slab, cache


def test_apply_is_the_references_forward(served, tokens, full):
    model, params = served
    got = np.asarray(model.apply(params, jnp.asarray(tokens)[None]))[0]
    assert np.abs(got - full[0]).max() < TIGHT * full[0].std()
    # the seeded sinks matter: a fifth of a head's mass by the draw's mean
    assert 0.05 < reference.sink_mass(params, model.cfg, tokens) < 0.6


def test_the_cache_is_laid_out_by_kind_of_layer(served):
    model, _ = served
    cache = model.init_cache(LANES)
    # key rows of whole registers (24 -> 128), values as they are; the full
    # layers max_seq long with their KV heads, the rings a window long
    assert [a.shape for a in cache["k"]] == [(LANES, 1, 96, 128)] * 2
    assert [a.shape for a in cache["v"]] == [(LANES, 1, 96, 16)] * 2
    assert [a.shape for a in cache["wk"]] == [(LANES, 2, W, 128)] * 5
    assert [a.shape for a in cache["wv"]] == [(LANES, 2, W, 16)] * 5
    assert model.attention_kinds() == ((2, None), (5, W))
    assert len(model.position_layers(cache)) == 14
    # a parked lane's position lies past the full layers' rows
    assert model.park_index(cache) == 96
    # a position costs its rows in the full layers; the rings a fixed term
    row, ring_row = 2 * (128 + 16) * 4, 5 * 2 * (128 + 16) * 4
    assert model.cache_position_bytes(cache) == row
    price = model.lane_cache_bytes(cache)
    assert [price(n) for n in (0, 1, W, 50)] == [
        0, row + ring_row, W * (row + ring_row), 50 * row + W * ring_row]
    # a prompt's slab: its full layers' rows and the LAST window of rows
    at_bf16 = lambda rows: rows // 2  # noqa: E731 (the formula is bfloat16's)
    assert model.prefill_slab_bytes(2, 64) == at_bf16(2 * (64 * row + W * ring_row))
    assert model.prefill_slab_bytes(1, 8) == at_bf16(8 * (row + ring_row))
    assert model.kv_bytes_per_token() == at_bf16(row)


@pytest.mark.parametrize("bucket,lens", [
    (32, (20, 29, 7)), (64, (15, 16, 17)), (64, (33, 48, 64)), (8, (1, 8, 5))])
def test_prefill_then_decode_through_the_cache_is_the_references_forward(
        served, tokens, full, bucket, lens):
    """Prompts right-padded to a bucket, at lengths on both sides of the
    window and of its multiples, laid into lanes with idle ones among
    them; then 20 steps, through every multiple of the window each lane
    crosses (the ring wraps once or twice): logits at every step, and the
    ring's rows themselves the reference's last keys and values."""
    model, params = served
    lanes = (4, 1, 2)
    logits, _slab, cache = _filled(model, params, tokens, lens, bucket, lanes)
    scale = full[0].std()
    for i, n in enumerate(lens):
        assert np.abs(logits[i] - full[0][n - 1]).max() < TIGHT * scale
    window_layers = [l for l, kind in enumerate(KINDS) if kind != "full_attention"]
    full_layers = [l for l, kind in enumerate(KINDS) if kind == "full_attention"]

    def rings_hold(cache, held):
        for at, l in enumerate(window_layers):
            ref_k, ref_v = full[3][l]
            for lane, n in zip(lanes, held):
                for s in range(min(n, W)):
                    p = (n - 1) - ((n - 1 - s) % W)
                    np.testing.assert_allclose(
                        np.asarray(cache["wk"][at][lane, :, s, :24]), ref_k[p],
                        atol=2e-5)
                    np.testing.assert_allclose(
                        np.asarray(cache["wv"][at][lane, :, s]), ref_v[p],
                        atol=2e-5)
                assert not np.asarray(cache["wk"][at][lane, :, :, 24:]).any()

    rings_hold(cache, lens)
    live = np.isin(np.arange(LANES), lanes)
    pos = np.zeros(LANES, np.int32)
    pos[list(lanes)] = lens
    idle_before = [np.asarray(a)[~live] for a in cache["wk"] + cache["k"]]
    step = jax.jit(model.decode_step_cache)
    for _ in range(20):
        tok = np.where(live, tokens[np.minimum(pos, 95)], 0)[:, None]
        lg, cache, counts = step(
            params, cache, jnp.asarray(tok, jnp.int32), jnp.asarray(pos),
            lens=jnp.asarray(np.where(live, pos + 1, 0)),
            write_pos=jnp.asarray(np.where(live, pos, 96)))
        for lane in lanes:
            assert np.abs(np.asarray(lg[lane]) - full[0][pos[lane]]).max() < (
                TIGHT * scale), (lane, pos[lane])
        now = pos[live] + 1
        assert np.asarray(counts)[4:].tolist() == [
            LANES * 96 * 2, int(now.sum()) * 2, LANES * W * 5,
            int(np.minimum(now, W).sum()) * 5, int(now.sum()) * 5]
        pos[live] += 1
    rings_hold(cache, [n + 20 for n in lens])
    for at, l in enumerate(full_layers):
        for lane, n in zip(lanes, lens):
            np.testing.assert_allclose(
                np.asarray(cache["k"][at][lane, :, :n + 20, :24]),
                np.moveaxis(full[3][l][0][:n + 20], 0, 1), atol=2e-5)
    # an idle lane's rows and rings are what they were
    for a, b in zip(idle_before, [np.asarray(a)[~live]
                                  for a in cache["wk"] + cache["k"]]):
        assert np.array_equal(a, b)


def test_a_parked_lane_writes_in_neither_kind(served, tokens):
    """``write_pos = park_index`` with ``lens > 0`` (the stop-aware burst's
    done lane keeps its length until the host reads it): the full layers
    drop the row past their end, and the ring is told by the position, not
    by ``park mod window`` (0: a live slot)."""
    model, params = served
    _logits, _slab, cache = _filled(model, params, tokens, (40,), 64, (3,))
    before = jax.tree_util.tree_map(np.asarray, cache)
    pos = jnp.asarray([0, 0, 0, 40, 0, 0], jnp.int32)
    _lg, after, _counts = model.decode_step_cache(
        params, cache, jnp.zeros((LANES, 1), jnp.int32), pos,
        lens=jnp.where(jnp.arange(LANES) == 3, 41, 0),
        write_pos=jnp.full((LANES,), model.park_index(cache), jnp.int32))
    for name in before:
        for a, b in zip(before[name], after[name]):
            assert np.array_equal(a, np.asarray(b)), name


@pytest.mark.parametrize("variant", reference.VARIANTS)
def test_a_wrong_model_is_told_from_the_served_one(served, tokens, full, variant):
    """Each control computes another function: no sink, a sink on the full
    layers too, one rotary base for both kinds, rotary over the whole head,
    interleaved pairs, a window of 127 / 129 (here 15 / 17: one row off),
    no value scale, the two KV head counts' grouping swapped, 8-bit
    weights. The served logits agree with none of them."""
    model, params = served
    cfg = model.cfg
    if variant in ("window_127", "window_129"):
        # the control names the published window's neighbours; at this size
        # the neighbours of 16
        off = -1 if variant == "window_127" else 1
        cfg = dataclasses.replace(cfg, swa_window=W + off)
        variant = ""
    wrong = reference.forward(params, cfg, tokens, list(range(len(tokens))),
                              variant)[0]
    got = np.asarray(model.apply(params, jnp.asarray(tokens)[None]))[0]
    assert np.abs(got - wrong).max() > 30 * TIGHT * full[0].std()


def test_bfloat16_where_float32_is_stated_is_told_apart(served, tokens, full):
    """The same weights served in bfloat16 miss the float32 limit by two
    orders: the limit would see a path that computed in a lower precision
    than it states."""
    model, params = served
    low = DecoderLM(**dict(SMALL, dtype="bfloat16"))
    got = np.asarray(low.apply(params, jnp.asarray(tokens)[None]))[0]
    assert np.abs(got - full[0]).max() > 100 * TIGHT * full[0].std()


def test_the_sixteen_shares_add_up_to_the_uncut_layer(tokens):
    """Guide section 4: 32 experts held 2 a chip by sixteen chips. The routed
    part of one expert layer as each share's reference computes it, summed
    over the shares, is the uncut reference's routed output; the served
    model's layer over one share is that share's part."""
    uncut = DecoderLM(**dict(SMALL, experts_held=None))
    params = uncut.init_params(5)
    layer = params["layers"][2]
    m = np.random.default_rng(1).normal(size=(40, 64)).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        whole, picks, _ = reference._routed_ffn(jnp.asarray(m), layer, uncut.cfg, None)
        parts = []
        for chip in range(16):
            held = (2 * chip, 2)
            share = dict(layer, **{n: layer[n][2 * chip:2 * chip + 2]
                                   for n in ("we1", "we3", "we2")})
            part, own, _ = reference._routed_ffn(
                jnp.asarray(m), share, uncut.cfg, held)
            assert np.array_equal(np.asarray(own), np.asarray(picks))
            parts.append(np.asarray(part))
            if chip == 5:
                one = DecoderLM(**dict(SMALL, experts_held=held))
                x = jnp.asarray(m)[None]
                served_part = one._ffn(dict(share, ln_ffn=jnp.ones((64,))),
                                       x, True, real=None)[0] - x
                normed = reference._norm(jnp.asarray(m), 1.0, one.cfg.norm_eps)
                want, _, _ = reference._routed_ffn(normed, share, one.cfg, held)
                np.testing.assert_allclose(np.asarray(served_part[0]),
                                           np.asarray(want), atol=2e-5)
    np.testing.assert_allclose(sum(parts), np.asarray(whole), atol=2e-5)
    assert np.abs(np.asarray(whole)).max() > 0.1


def test_the_step_through_the_kernels_interpreted(served, tokens, monkeypatch):
    """The step as a lowering for a TPU runs it: both reads through the
    ragged kernel (interpreted), the ring's with its sink under its own
    name. Live lanes' logits are the dots' step's, the caches bit for bit
    on the live lanes, and the counters count the kernel's walk: a live
    lane's length in whole blocks, a ring's one block."""
    import importlib

    mod = importlib.import_module("seldon_core_tpu.ops.decode_attention")
    names = []
    kernel = mod.ragged_decode_attention

    def interpreted(*a, name=None, **kw):
        names.append(name)
        return kernel(*a, **kw, name=name, interpret=True)

    wide = dict(SMALL, max_seq=256, swa_window=128)
    model = DecoderLM(**wide)
    params = model.init_params(3)
    long = np.random.default_rng(2).integers(0, 96, size=200)
    lens, lanes = (130, 40, 199), (0, 2, 5)
    _lg, _slab, cache = _filled(model, params, long, lens, 256, lanes)
    live = np.isin(np.arange(LANES), lanes)
    pos = np.zeros(LANES, np.int32)
    pos[list(lanes)] = lens
    args = (jnp.asarray(np.where(live, long[np.minimum(pos, 199)], 0)[:, None],
                        jnp.int32), jnp.asarray(pos))
    how = dict(lens=jnp.asarray(np.where(live, pos + 1, 0)))
    dots, dcache, dcounts = model.decode_step_cache(params, cache, *args, **how)
    monkeypatch.setattr(mod.lax, "platform_dependent",
                        lambda *args, tpu, default: tpu(*args))
    monkeypatch.setattr(
        mod, "reads_ragged", lambda platform, *a, **kw: True)
    monkeypatch.setattr(mod, "ragged_decode_attention", interpreted)
    # unjitted: the jitted entry would answer from a trace made before the
    # patches
    import seldon_core_tpu.ops as ops
    monkeypatch.setattr(ops, "decode_attention", mod.decode_attention.__wrapped__)
    import seldon_core_tpu.models.mimo_v2 as family
    monkeypatch.setattr(family.MimoV2LM, "_reads", lambda self, cache, lens_,
                        ring_lens, attn_len, mesh: [
        jnp.sum(-(-lens_ // 256) * 256) * 2,
        jnp.sum(-(-ring_lens // 128) * 128) * 5])
    got, kcache, kcounts = model.decode_step_cache(params, cache, *args, **how)
    assert names.count("swa_ring_attention") == 5 and names.count(None) == 2
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(dots)[live],
                               atol=1e-4)
    # the first layer's rows are the same on both paths, bit for bit; a
    # later layer's differ by what the reads' roundings moved its input
    for name in ("k", "v"):
        assert np.array_equal(np.asarray(dcache[name][0])[live],
                              np.asarray(kcache[name][0])[live])
    for name in dcache:
        for a, b in zip(dcache[name], kcache[name]):
            np.testing.assert_allclose(np.asarray(a)[live], np.asarray(b)[live],
                                       atol=1e-4)
    assert np.asarray(kcounts)[[4, 6]].tolist() == [(256 * 3) * 2, (128 * 3) * 5]
    assert np.array_equal(np.asarray(kcounts)[[0, 1, 2, 3, 5, 7, 8]],
                          np.asarray(dcounts)[[0, 1, 2, 3, 5, 7, 8]])


def test_what_the_family_refuses_and_what_it_asks():
    model = DecoderLM(**SMALL)
    for feature in ("speculation", "mesh", "kv_tier", "prefix_cache",
                    "chunked_prefill", "preemption", "migration"):
        with pytest.raises(UnsupportedByModel, match=feature):
            model.check_serves(**{feature: True})
    with pytest.raises(ValueError, match="layer_types"):
        DecoderLM(**dict(SMALL, layer_types=KINDS[:3]))
    with pytest.raises(ValueError, match="at least one full_attention"):
        DecoderLM(**dict(SMALL, layer_types=["sliding_attention"] * 7))
    with pytest.raises(ValueError, match="GQA"):
        DecoderLM(**dict(SMALL, swa_n_kv_heads=3))
    with pytest.raises(ValueError, match="experts_held"):
        DecoderLM(**dict(SMALL, experts_held=(30, 4)))
    # eight prompts of the batcher's longest own bucket do not share a call
    assert [model.prefill_rows_max(b) for b in (512, 1024, 1792, 9728)] == [
        8, 8, 4, 1]
    assert model.prefill_rows_max(512, added=True) == 1
    assert model.n_params() > 0 and model.flops_per_token(100) > 0
    assert model.dispatch_read_bytes("fused_burst", rows=4, live=2, k=3,
                                     bucket=64) > 0
