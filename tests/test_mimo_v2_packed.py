"""The mimo_v2 family where its keys are held CUT AND PACKED (ISSUE 59): a
key of 192 as a 128-wide part in its head's own row and a 64-wide rest
beside another head's in one of KV / 2 rows after them, 2,560 B a position
and full layer for the 3,072 of a row padded to 256. A small configuration
that meets the rule on the shapes (``head_dim`` 192, ``v_head_width`` 128,
2 and 4 KV heads, both kinds of layer) against the plain reference
(``benchmark/reference/mimo_v2.py``) in float32 on the CPU: prefill then
decode through the cache is the reference's one forward, from the stored
tree and from the burst's (``wq`` / ``wk`` in the cut's order);
``cached_rows`` gives back the keys a prefill projected, bit for bit, from
the serving cache and from a slab; the sizes follow the row; and a shape
that does not meet the rule keeps its padded row."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import mimo_v2 as reference
from seldon_core_tpu.models.llm import DecoderLM
from seldon_core_tpu.models.mimo_v2 import MimoV2LM
from seldon_core_tpu.ops.decode_attention import (
    pack_keys,
    packed_key_rows,
    walk_block,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
KINDS = (["full_attention"] + ["sliding_attention"] * 4
         + ["full_attention", "sliding_attention"])
PACKED = dict(
    block="mimo_v2", vocab_size=96, d_model=64, n_layers=7, n_heads=8,
    n_kv_heads=2, head_dim=192, d_ff=128, max_seq=96, layer_types=KINDS,
    rope_theta=1e7, swa_rope_theta=1e4,
    v_head_width=128, rotary_dim=64, swa_window=16, swa_n_kv_heads=4,
    n_dense_layers=1, n_routed_experts=32, experts_per_tok=4, expert_width=32,
    experts_held=(4, 4), dtype="float32", residual_scale=0.5)
W = 16
LANES = 6
TIGHT = 1e-4        # tests/test_mimo_v2.py's: float32 against float32
FULL = [l for l, kind in enumerate(KINDS) if kind == "full_attention"]
WINDOW = [l for l, kind in enumerate(KINDS) if kind != "full_attention"]


@pytest.fixture(scope="module")
def served():
    model = DecoderLM(**PACKED)
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
    """``tests/test_mimo_v2.py``'s: the prompts' first ``lens`` tokens
    prefilled together in ``bucket`` and laid into ``lanes`` of a cache as
    the batcher's insert lays them, each leaf from 0 along every axis."""
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


def test_the_cache_holds_a_key_as_a_part_and_two_heads_rests_a_row(served):
    model, _ = served
    cache = model.init_cache(LANES)
    # 2 KV heads: 2 parts and 1 row of two rests; 4: 4 and 2; values as they are
    assert [a.shape for a in cache["k"]] == [(LANES, 3, 96, 128)] * 2
    assert [a.shape for a in cache["v"]] == [(LANES, 2, 96, 128)] * 2
    assert [a.shape for a in cache["wk"]] == [(LANES, 6, W, 128)] * 5
    assert [a.shape for a in cache["wv"]] == [(LANES, 4, W, 128)] * 5
    assert len(model.position_layers(cache)) == 14
    assert model.park_index(cache) == 96
    # nothing is padding: a position's bytes are its keys' and values' own
    row, ring_row = 2 * 2 * (192 + 128) * 4, 5 * 4 * (192 + 128) * 4
    assert model.cache_position_bytes(cache) == row
    price = model.lane_cache_bytes(cache)
    assert [price(n) for n in (0, 1, W, 50)] == [
        0, row + ring_row, W * (row + ring_row), 50 * row + W * ring_row]
    at_bf16 = lambda rows: rows // 2  # noqa: E731 (the formula is bfloat16's)
    assert model.row_bytes(False) == at_bf16(row) // 2
    assert model.row_bytes(True) == at_bf16(ring_row) // 5
    assert model.kv_bytes_per_token() == at_bf16(row)
    assert model.prefill_slab_bytes(2, 64) == at_bf16(2 * (64 * row + W * ring_row))


@pytest.mark.parametrize("tree", ["stored", "burst"])
@pytest.mark.parametrize("bucket,lens", [
    (32, (20, 29, 7)), (64, (15, 16, 17)), (64, (33, 48, 64))])
def test_prefill_then_decode_through_packed_rows_is_the_references_forward(
        served, tokens, full, bucket, lens, tree):
    """Prompts right-padded to a bucket, on both sides of the window and of
    its multiples, idle lanes among them; then 20 steps, the rings wrapping
    once or twice: the logits at every step are the reference's, from the
    stored tree (the heads rotated whole and cut after) and from the
    burst's (``wq`` / ``wk`` in the cut's order), and the rows and rings
    the steps leave are the reference's keys and values."""
    model, params = served
    lanes = (4, 1, 2)
    logits, _slab, cache = _filled(model, params, tokens, lens, bucket, lanes)
    scale = full[0].std()
    for i, n in enumerate(lens):
        assert np.abs(logits[i] - full[0][n - 1]).max() < TIGHT * scale
    if tree == "burst":
        params = model.burst_params(params)
        names = set(params["layers"][0])
        assert {"wq_cut_t", "wk_cut_t", "wv_t"} <= names
        assert not {"wq", "wk", "wv", "wq_t", "wk_t"} & names
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
    at = jnp.asarray(lanes, jnp.int32)
    for i, l in enumerate(FULL):
        n = min(lens) + 20
        k, v = model.cached_rows(cache, "full", i, at, jnp.broadcast_to(
            jnp.arange(n), (len(lanes), n)))
        ref_k, ref_v = full[3][l]
        for row in range(len(lanes)):
            np.testing.assert_allclose(np.asarray(k[row]), ref_k[:n], atol=2e-5)
            np.testing.assert_allclose(np.asarray(v[row]), ref_v[:n], atol=2e-5)
    held = np.asarray([n + 20 for n in lens])
    slot = np.arange(W)
    where = (held[:, None] - 1) - ((held[:, None] - 1 - slot[None]) % W)
    for i, l in enumerate(WINDOW):
        k, v = model.cached_rows(cache, "window", i, at, jnp.broadcast_to(
            jnp.arange(W), (len(lanes), W)))
        ref_k, ref_v = full[3][l]
        np.testing.assert_allclose(np.asarray(k), ref_k[where], atol=2e-5)
        np.testing.assert_allclose(np.asarray(v), ref_v[where], atol=2e-5)
    for a, b in zip(idle_before, [np.asarray(a)[~live]
                                  for a in cache["wk"] + cache["k"]]):
        assert np.array_equal(a, b)


def test_cached_rows_are_the_keys_the_prefill_projected_bit_for_bit(
        served, tokens, monkeypatch):
    """``cached_rows`` of a slab and of the serving cache the slab was laid
    into: the keys ``_forward`` handed ``_key_rows`` (as projected and
    rotated, whole) and its values, the stored bits; a ring's slots hold
    the last window of them."""
    model, params = served
    projected = []
    lay = model._key_rows

    def recorded(k):
        projected.append(np.asarray(k))
        return lay(k)

    monkeypatch.setattr(model, "_key_rows", recorded)
    lens, lanes, bucket = (20, 29, 7), (4, 1, 2), 32
    _logits, slab, cache = _filled(model, params, tokens, lens, bucket, lanes)
    assert len(projected) == 7 and projected[0].shape == (3, 2, bucket, 192)
    assert slab["k"].shape == (2, 3, 3, bucket, 128)
    assert slab["wk"].shape == (5, 3, 6, W, 128)
    rows = jnp.arange(len(lens), dtype=jnp.int32)
    at = jnp.asarray(lanes, jnp.int32)
    for i, l in enumerate(FULL):
        for n, row, lane in zip(lens, range(len(lens)), lanes):
            want = np.moveaxis(projected[l][row, :, :n], 0, 1)   # [n, KV, 192]
            for held, who in ((slab, rows[row:row + 1]), (cache, at[row:row + 1])):
                k, _v = model.cached_rows(held, "full", i, who,
                                          jnp.arange(n)[None])
                assert k.shape == (1, n, 2, 192)
                assert np.array_equal(np.asarray(k[0]), want)
    for i, l in enumerate(WINDOW):
        for n, row, lane in zip(lens, range(len(lens)), lanes):
            slots = np.arange(min(n, W))
            where = (n - 1) - ((n - 1 - slots) % W)
            want = np.moveaxis(projected[l][row], 0, 1)[where]    # [., KV, 192]
            for held, who in ((slab, rows[row:row + 1]), (cache, at[row:row + 1])):
                k, v = model.cached_rows(held, "window", i, who,
                                         jnp.asarray(slots)[None])
                assert k.shape == (1, len(slots), 4, 192)
                assert v.shape == (1, len(slots), 4, 128)
                assert np.array_equal(np.asarray(k[0]), want)


def _published():
    from benchmark import manifest

    with open(os.path.join(ROOT, "benchmark", "configs", "mimo-v2.5.json")) as f:
        cfg = json.load(f)
    cfg["name"] = "mimo-v2.5"
    kwargs = manifest.architecture(
        ROOT, manifest.load(ROOT), cfg["architecture"]).model_kwargs(cfg, 0)
    kwargs.pop("seed")
    return cfg, DecoderLM(**kwargs)


def test_a_position_is_2560_bytes_and_a_ring_row_5120_at_the_published_shapes():
    """``init_cache(64, 12288)`` by its shapes alone: the full layers hold 4
    parts and 2 rows of rests a position beside 4 value rows, the rings 8, 4
    and 8, and what the family states of a row is what the arrays hold."""
    cfg, model = _published()
    lanes, T = cfg["server"]["slots"], cfg["server"]["max_seq"]
    assert (lanes, T) == (64, 12288)
    cache = jax.eval_shape(lambda: model.init_cache(lanes, T))
    assert [a.shape for a in cache["k"]] == [(64, 6, T, 128)] * 2
    assert [a.shape for a in cache["v"]] == [(64, 4, T, 128)] * 2
    assert [a.shape for a in cache["wk"]] == [(64, 12, 128, 128)] * 5
    assert [a.shape for a in cache["wv"]] == [(64, 8, 128, 128)] * 5

    def a_row(k, v):
        return sum(a.dtype.itemsize * a.size // (a.shape[0] * a.shape[2])
                   for a in (k, v))

    assert a_row(cache["k"][0], cache["v"][0]) == 2560 == model.row_bytes(False)
    assert a_row(cache["wk"][0], cache["wv"][0]) == 5120 == model.row_bytes(True)
    assert model.kv_bytes_per_token() == 2 * 2560
    assert model.prefill_slab_bytes(1, 9728) == 9728 * 5120 + 128 * 5 * 5120
    assert model.decode_bytes_per_token(1000, 64) - model.decode_bytes_per_token(
        999, 64) == 2 * 2560
    # the rings hold what the benchmark's comparison asks of them, no less
    rings = sum(a.size for a in cache["wk"] + cache["wv"])
    assert rings == lanes * 5 * 128 * 8 * 320


def test_the_walks_block_is_asked_with_the_bytes_the_call_copies():
    """``read_block``: ``walk_block`` at the full layers' shapes, fed what
    128 positions of K and V hold. At the published shapes 4 x 320 x 2 x 128
    = 327,680 B, under ``COVERS``: 256 keys a block (as the padded row's
    393,216); the rings' 655,360 would walk 128, and a ring is one block."""
    _cfg, model = _published()
    assert model.read_block(12288) == 256 == walk_block(
        4, 192, jnp.bfloat16, 12288, 128)
    assert model.read_block(12288 + 128) == 128      # 256 does not divide it
    assert walk_block(8, 192, jnp.bfloat16, 128, 128) == 128
    # a tiny size's 2 x (128 + 16) x 4 x 128 = 147,456 B double twice
    tiny = DecoderLM(**dict(PACKED, head_dim=24, v_head_width=16, rotary_dim=8))
    assert tiny.read_block(1024) == 512 == walk_block(
        2, 128, jnp.float32, 1024, 16)


@pytest.mark.parametrize("why,change,k,wk", [
    ("a key of 24 has no 128-wide part: padded to 128",
     dict(head_dim=24, rotary_dim=8), (LANES, 2, 96, 128), (LANES, 4, W, 128)),
    ("one KV head fills no row of rests: padded to 256; four pack",
     dict(n_kv_heads=1), (LANES, 1, 96, 256), (LANES, 6, W, 128)),
    ("a rest of 72 does not divide 128: padded to 256",
     dict(head_dim=200), (LANES, 2, 96, 256), (LANES, 4, W, 256)),
    ("a key of 256 is two whole rows: as it is",
     dict(head_dim=256), (LANES, 2, 96, 256), (LANES, 4, W, 256)),
    ("a rest of 32 packs four heads a row: the full layers' two do not",
     dict(head_dim=160), (LANES, 2, 96, 256), (LANES, 5, W, 128)),
])
def test_a_shape_that_does_not_meet_the_rule_keeps_its_row(why, change, k, wk,
                                                           tokens):
    """The rule is on the shapes, a kind at a time; either way the served
    path is the reference's forward."""
    model = DecoderLM(**dict(PACKED, **change))
    cache = model.init_cache(LANES)
    assert cache["k"][0].shape == k, why
    assert cache["wk"][0].shape == wk, why
    cfg = model.cfg
    assert [bool(model._packed[w]) for w in (False, True)] == [
        k[1] != cfg.n_kv_heads, wk[1] != cfg.swa_n_kv_heads]
    params = model.init_params(5)
    want = reference.forward(params, cfg, tokens[:40], list(range(40)))[0]
    lens, lanes = (20, 33), (3, 0)
    logits, _slab, cache = _filled(model, params, tokens, lens, 64, lanes)
    pos = np.zeros(LANES, np.int32)
    pos[list(lanes)] = lens
    live = pos > 0
    for _ in range(3):
        lg, cache, _counts = model.decode_step_cache(
            model.burst_params(params), cache,
            jnp.asarray(np.where(live, tokens[pos], 0)[:, None], jnp.int32),
            jnp.asarray(pos), lens=jnp.asarray(np.where(live, pos + 1, 0)))
        for lane in lanes:
            assert np.abs(np.asarray(lg[lane]) - want[pos[lane]]).max() < (
                TIGHT * want.std()), (why, lane)
        pos[live] += 1


def test_the_rule_on_the_shapes():
    assert packed_key_rows(192, 4) == 2 and packed_key_rows(192, 8) == 4
    assert packed_key_rows(160, 8) == 2
    for head_dim, kv in ((192, 1), (192, 3), (128, 4), (64, 4), (256, 4),
                         (320, 4), (200, 4), (24, 2)):
        assert packed_key_rows(head_dim, kv) == 0, (head_dim, kv)
    k = jnp.arange(2 * 4 * 3 * 192, dtype=jnp.float32).reshape(2, 4, 3, 192)
    rows = pack_keys(k, 2)
    assert rows.shape == (2, 6, 3, 128)
    assert jnp.array_equal(rows[:, :4], k[..., :128])
    # row j of the rests: head 2j's beside head 2j + 1's
    assert jnp.array_equal(rows[:, 4, :, :64], k[:, 0, :, 128:])
    assert jnp.array_equal(rows[:, 4, :, 64:], k[:, 1, :, 128:])
    assert jnp.array_equal(rows[:, 5, :, 64:], k[:, 3, :, 128:])


@pytest.mark.parametrize("tree", ["stored", "burst"])
def test_the_step_through_the_kernels_interpreted_over_packed_rows(
        tokens, monkeypatch, tree):
    """The step as a lowering for a TPU runs it: both reads through the
    ragged kernel (interpreted) over packed key rows, handed the queries'
    rests, the ring's with its sink under its own name. Live lanes' logits
    are the dots' step's, the caches bit for bit in the first layer, and the
    counters count the kernel's walk."""
    import importlib

    mod = importlib.import_module("seldon_core_tpu.ops.decode_attention")
    calls = []
    kernel = mod.ragged_decode_attention

    def interpreted(q, k, *a, name=None, q_rest=None, **kw):
        calls.append((name, q.shape, k.shape, q_rest.shape))
        return kernel(q, k, *a, **kw, name=name, q_rest=q_rest, interpret=True)

    model = DecoderLM(**dict(PACKED, max_seq=256, swa_window=128))
    params = model.init_params(3)
    long = np.random.default_rng(2).integers(0, 96, size=200)
    lens, lanes = (130, 40, 199), (0, 2, 5)
    _lg, _slab, cache = _filled(model, params, long, lens, 256, lanes)
    if tree == "burst":
        params = model.burst_params(params)
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
    assert [c for c in calls if c[0] is None] == [
        (None, (LANES, 8, 1, 128), (LANES, 3, 256, 128), (LANES, 8, 1, 64))] * 2
    assert [c for c in calls if c[0] == "swa_ring_attention"] == [
        ("swa_ring_attention", (LANES, 8, 1, 128), (LANES, 6, 128, 128),
         (LANES, 8, 1, 64))] * 5
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(dots)[live],
                               atol=1e-4)
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


@pytest.fixture(scope="module")
def batched():
    from seldon_core_tpu.serving.continuous import ContinuousBatcher

    model = DecoderLM(**dict(PACKED, vocab_size=97, max_seq=256))
    params = model.init_params(3)
    keep = ContinuousBatcher.MIN_ATTN_BUCKET
    ContinuousBatcher.MIN_ATTN_BUCKET = 16
    batcher = ContinuousBatcher(
        model, params, slots=4, max_seq=256, prefill_buckets=(16, 32, 64),
        steps_per_poll=4, attn_bucket=16)
    yield model, params, batcher
    batcher.close()
    ContinuousBatcher.MIN_ATTN_BUCKET = keep


def test_the_batchers_cache_and_prices_are_the_packed_rows(batched):
    _model, _params, batcher = batched
    cache = batcher._cache
    assert [a.shape for a in cache["k"]] == [(4, 3, 256, 128)] * 2
    assert [a.shape for a in cache["wk"]] == [(4, 6, 16, 128)] * 5
    assert batcher._position_layers == 14
    assert batcher._kv_key_bytes == 2 * 2 * (192 + 128) * 4
    assert batcher._lane_bytes(100) == 100 * batcher._kv_key_bytes + (
        16 * 5 * 4 * (192 + 128) * 4)
    # the burst's tree holds wq / wk in the cut's order
    assert "wq_cut_t" in batcher._burst_params["layers"][0]


@pytest.mark.parametrize("n,new", [(1, 20), (16, 3), (17, 18), (64, 3),
                                   (100, 14)])
def test_greedy_tokens_through_the_batcher_are_the_reference_loops(
        batched, n, new):
    """``tests/test_mimo_v2_batcher.py``'s lengths at packed key rows: the
    batcher's own prefill, insert and fused burst (on the burst's tree)
    against the plain reference's generation loop."""
    model, params, batcher = batched
    prompt = [int(t) for t in
              np.random.default_rng(10 + n).integers(0, 97, size=n)]
    got = batcher.submit(prompt, max_new_tokens=new).result(timeout=600)
    assert got[:n] == prompt
    assert got[n:] == reference.generate(params, model.cfg, prompt, new)


def test_the_benchmarks_comparison_is_answered_by_the_family_over_packed_rows():
    """``benchmark/architectures/mimo_v2.py:compare_served`` at the cell's
    rehearsal size with the published KEY widths kept (192 beside 128, 2
    and 4 KV heads: the shapes meet the rule), in bfloat16, on a batcher's
    own prefills, insert and fused burst: the rows and the rings are asked
    of ``MimoV2LM.cached_rows``, the counted block of ``read_block``, and
    every limit the cell holds on the chip holds here."""
    from benchmark import manifest
    from seldon_core_tpu.serving.continuous import ContinuousBatcher

    with open(os.path.join(ROOT, "benchmark", "configs", "mimo-v2.5.json")) as f:
        cfg = json.load(f)
    cfg["name"] = "mimo-v2.5"
    arch = manifest.architecture(ROOT, manifest.load(ROOT), cfg["architecture"])
    small = dict(cfg, **arch.rehearsal(cfg), name="tiny")
    small.update(head_dim=192, swa_head_dim=192, v_head_dim=128,
                 swa_v_head_dim=128, num_key_value_heads=2,
                 swa_num_key_value_heads=4)
    kw = arch.model_kwargs(small, 7)
    seed = kw.pop("seed")
    model = arch.SeededMimoV2LM(**kw)
    assert type(model).cached_rows is MimoV2LM.cached_rows
    assert type(model).read_block is MimoV2LM.read_block
    params = model.init_params(seed)
    batcher = ContinuousBatcher(model, params, slots=32, max_seq=1024,
                                steps_per_poll=4)
    try:
        cache = batcher._cache
        assert [a.shape for a in cache["k"]] == [(32, 3, 1024, 128)] * 2
        assert [a.shape for a in cache["wk"]] == [(32, 6, 16, 128)] * 5
        assert arch.window_leaf_names(cache, model.cfg, 32, 1024) == ("wk", "wv")
        batcher._warm_args = {"prompt_lens": (100, 300), "max_new_tokens": 216,
                              "batch_sizes": (1, 4, 8)}
        out = arch.compare_served(model, params, seed=2**31 + 3, batcher=batcher)
    finally:
        batcher.close()
    assert out["ok"], out
    # 2 KV heads of 192 + 128 in bfloat16: 512 keys copy 655,360 B
    assert out["read_block"] == 512 and out["lanes_wrapped"] > 0
    assert out["rows_ratio"] <= arch.ROWS_TOLERANCE
    assert out["rings_ratio"] <= arch.RINGS_TOLERANCE
    assert out["idle_untouched"] and out["inserted"]
    assert out["counters_are_the_picks"] and out["burst_counters_hold"]
