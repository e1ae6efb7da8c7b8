"""The lfm2_moe block (LFM2-24B-A2B's layers) at a small size on the CPU,
seeded random weights, against the benchmark's plain reference: batched
prefill of right-padded prompts and then decode through the batcher's
cache (logits, K and V rows, convolution tails), idle lanes among live
ones, the shares of an expert layer, the selection bias, the typed
refusals. The ops it brought: ``tests/test_decode_attention.py`` holds the
128 and 256 paths, this file the packed rows of two heads of 64."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import lfm2_moe as reference
from seldon_core_tpu.models.llm import DecoderLM, UnsupportedByModel
from seldon_core_tpu.ops import experts, gated_delta
from seldon_core_tpu.ops.decode_attention import (
    cache_attention, ragged_decode_attention, walk_block)
from seldon_core_tpu.serving.continuous import ContinuousBatcher

# one leading dense layer, then the published period of four
KINDS = ["conv", "full_attention", "conv", "conv", "conv", "full_attention"]
SMALL = dict(
    block="lfm2_moe", vocab_size=256, d_model=128, n_layers=6, n_heads=4,
    n_kv_heads=2, head_dim=64, d_ff=256, max_seq=512, rope_theta=1e6,
    norm_eps=1e-5, dtype="float32", layer_types=KINDS, n_dense_layers=1,
    n_routed_experts=16, experts_per_tok=4, expert_width=64,
    experts_held=(4, 4), conv_kernel=3, residual_scale=0.5)
BLOCK = 256     # the kernel's block at the cell's rows, and at 2 rows of 128 in
#                 float32: lengths lie on both sides
WALKED = 512    # its block at SMALL's ONE row of 128 in float32 (128 keys of
#                 K and V: 128 KiB; 512 copy 512 KiB): the whole of this cache


@pytest.fixture(scope="module")
def served():
    model = DecoderLM(**SMALL)
    return model, model.init_params(3)


@pytest.fixture(scope="module")
def batcher(served):
    model, params = served
    b = ContinuousBatcher(model, params, slots=4, max_seq=512)
    yield b
    b.close()


def test_the_block_is_built_through_decoderlm_and_counts_its_parameters(served):
    model, params = served
    assert type(model).__name__ == "Lfm2MoeLM"
    assert model.n_params() == sum(
        a.size for a in jax.tree_util.tree_leaves(params))
    # 2 attention layers, not 6: the convolution layers read no positions
    assert model.attention_kinds() == ((2, None),)
    cache = model.init_cache(4, 512)
    assert {n: len(v) for n, v in cache.items()} == {"k": 2, "v": 2, "conv": 4}
    # two heads of 64 a row of 128
    assert cache["k"][0].shape == (4, 1, 512, 128)
    assert cache["conv"][0].shape == (4, 2, 128)
    assert walk_block(1, 128, cache["k"][0].dtype, 512) == WALKED
    assert walk_block(2, 128, cache["k"][0].dtype, 512) == BLOCK
    assert model.kv_bytes_per_token() == 2 * 2 * 2 * 64 * 2
    lane_bytes = model.lane_cache_bytes(cache)
    per_position = model.cache_position_bytes(cache)
    tails = 4 * 2 * 128 * 4
    assert per_position == 2 * 2 * 128 * 4
    assert (lane_bytes(0), lane_bytes(1), lane_bytes(100)) == (
        0, per_position + tails, 100 * per_position + tails)
    assert len(model.position_layers(cache)) == 4
    assert model.prefill_slab_bytes(2, 128) == 2 * (
        128 * model.kv_bytes_per_token() + model.tail_bytes_per_lane())
    # the batcher's buckets, then every 512 up to the cache's length
    assert model.prefill_lengths((32, 128, 512, 1024, 1792), 4096) == (
        32, 128, 512, 1024, 1792, 2048, 2560, 3072, 3584)
    assert model.prefill_lengths((32, 128), 256) == (32, 128)
    assert [model.prefill_rows_max(b) for b in (1792, 2048, 4608, 8192, 12288,
                                                16384)] == [8, 8, 3, 2, 1, 1]
    assert model.admissions_per_turn() == 0 and model.block_tokens() == 1


# lane -> tokens it holds before its first step: shorter than the
# convolution (1, 2), as long (3), and on both sides of the kernel's block
LENGTHS = {0: 1, 1: 2, 2: 3, 4: BLOCK - 2, 5: BLOCK - 1, 6: BLOCK, 7: 300}
STEPS = 3


@pytest.fixture(scope="module")
def stepped(served):
    """Every lane's prompt through ONE batched prefill program a bucket
    (right-padded, each row's ``last_index`` its own), the batcher's own
    ``insert_many`` into a cache of 8 lanes (lane 3 idle and never
    admitted), then ``STEPS`` decode steps of all lanes."""
    model, params = served
    b = ContinuousBatcher(model, params, slots=8, max_seq=512)
    rng = np.random.default_rng(0)
    toks = {lane: rng.integers(0, 256, size=n + STEPS)
            for lane, n in LENGTHS.items()}
    cache = model.init_cache(8, 512)
    # what an idle lane holds is some earlier occupant's: not zeros
    cache = jax.tree_util.tree_map(
        lambda a: a.at[3].set(jnp.asarray(
            rng.normal(size=a.shape[1:]), a.dtype)), cache)
    regs = (jnp.zeros((8,), jnp.int32), jnp.zeros((8,), jnp.int32),
            jnp.zeros((8, 2), jnp.uint32))
    first = {}
    prefill = jax.jit(lambda p, t, li: model.prefill(p, t, t.shape[1], li))
    for bucket, lanes in ((32, [0, 1, 2]), (512, [4, 5, 6, 7])):
        prompts = np.zeros((len(lanes), bucket), np.int32)
        for row, lane in enumerate(lanes):
            prompts[row, :LENGTHS[lane]] = toks[lane][:LENGTHS[lane]]
        last = jnp.asarray([LENGTHS[lane] - 1 for lane in lanes], jnp.int32)
        logits, slab = prefill(params, jnp.asarray(prompts), last)
        cache, *_ = b._insert_many_fn(
            cache, slab, jnp.asarray(lanes, jnp.int32),
            jnp.zeros((len(lanes),), jnp.int32), last + 1,
            jnp.zeros((len(lanes), 2), jnp.uint32), *regs)
        first.update({lane: np.asarray(logits[row])
                      for row, lane in enumerate(lanes)})
    idle_before = jax.tree_util.tree_map(lambda a: np.asarray(a[3]), cache)
    step = jax.jit(model.decode_step_cache)
    live = np.array([lane in LENGTHS for lane in range(8)])
    at = np.array([LENGTHS.get(lane, 0) for lane in range(8)])
    outs = []
    for i in range(STEPS):
        pos = np.where(live, at + i, 0)
        tokens = np.array([toks[lane][at[lane] + i] if live[lane] else 0
                           for lane in range(8)])
        out, cache, counts = step(
            params, cache, jnp.asarray(tokens[:, None], jnp.int32),
            jnp.asarray(pos, jnp.int32),
            lens=jnp.asarray(np.where(live, pos + 1, 0), jnp.int32))
        outs.append((np.asarray(out), np.asarray(counts)))
    b.close()
    want = {lane: reference.forward(
        params, model.cfg, toks[lane],
        list(range(n - 1, n + STEPS))) for lane, n in LENGTHS.items()}
    return dict(model=model, toks=toks, first=first, outs=outs, want=want,
                cache=jax.tree_util.tree_map(np.asarray, cache),
                idle_before=idle_before)


@pytest.mark.parametrize("lane", sorted(LENGTHS))
def test_prefill_then_decode_through_the_cache_is_the_references_forward(
        stepped, lane):
    """Logits and not tokens: the prefill's at the prompt's own last
    position, whatever it was padded to, and each step's."""
    want = stepped["want"][lane][0]
    np.testing.assert_allclose(stepped["first"][lane], want[0], atol=3e-4)
    for i, (out, _counts) in enumerate(stepped["outs"]):
        np.testing.assert_allclose(out[lane], want[1 + i], atol=3e-4)


@pytest.mark.parametrize("lane", sorted(LENGTHS))
def test_the_cache_holds_the_references_rows_and_tails(stepped, lane):
    """K and V rows, two heads a row, at every position the lane holds
    (the prefill's and the steps'), and each convolution layer's tail: the
    convolution's input at the lane's last two positions, zeros before a
    sequence's start."""
    model, cache = stepped["model"], stepped["cache"]
    n = LENGTHS[lane] + STEPS
    _logits, _picks, _scores, kv, z, _weights = stepped["want"][lane]
    for l, (k, v) in enumerate(kv):
        for name, rows in (("k", k), ("v", v)):
            # [T, KV, Dh] -> [KV / 2, T, 2 Dh]
            packed = rows.reshape(n, 1, 128).transpose(1, 0, 2)
            np.testing.assert_allclose(
                cache[name][l][lane][:, :n], packed, atol=2e-4)
    for l, zs in enumerate(z):
        tail = np.concatenate([np.zeros((2, 128), np.float32), zs])[-2:]
        np.testing.assert_allclose(cache["conv"][l][lane], tail, atol=2e-4)
    assert model._pack == 2


def test_an_idle_lanes_tail_and_rows_stay_and_the_step_counts_its_lanes(stepped):
    after = jax.tree_util.tree_map(lambda a: a[3], stepped["cache"])
    for name in ("conv", "k", "v"):
        for was, now in zip(stepped["idle_before"][name], after[name]):
            if name == "conv":
                np.testing.assert_array_equal(was, now)
            else:
                # off a TPU the scatter writes an idle lane's row at its
                # position (0), which no read admits; nothing else
                np.testing.assert_array_equal(was[:, 1:], now[:, 1:])
    for i, (_out, counts) in enumerate(stepped["outs"]):
        touched, routed, layer_steps, held, read, live, tails = counts
        lens = np.array(list(LENGTHS.values())) + i + 1
        # 7 live lanes x 4 picks x 5 expert layers; a tail a live lane and
        # conv layer; of the 2 attention layers, off a TPU, the dots read
        # the whole bound of all 8 lanes, and the count says so
        assert (routed, layer_steps, tails) == (7 * 4 * 5, 5, 7 * 4)
        assert live == 2 * lens.sum()
        assert read == 2 * 8 * 512
        assert 0 < touched <= held < routed
        # what the kernel's lowering counts: rounded as it walks
        walked = stepped["model"]._rows_walked(
            stepped["cache"]["k"][0], jnp.asarray(lens, jnp.int32))
        assert int(walked) == (-(-lens // WALKED) * WALKED).sum()


@pytest.mark.parametrize("lens", [
    [1, 0, BLOCK - 1, BLOCK, BLOCK + 1, 0, 40, 511],
    [300, 511, 2, 0, 0, 129, 256, 257]])
def test_the_kernel_over_two_heads_of_64_a_row_is_each_heads_own_attention(
        served, lens):
    """The ragged kernel (interpreted) at the packed layout, 4 KV heads of
    64 as 2 rows of 128, the queries times sqrt(2) under the op's own ``1 /
    sqrt(128)``, against plain per-head
    attention at 64 wide, and against the dots over the same packed rows;
    the row written where the lane's read ends."""
    model = DecoderLM(**dict(SMALL, n_heads=8, n_kv_heads=4))
    rng = np.random.default_rng(5)
    B, H, KV, T, Dh = len(lens), 8, 4, 512, 64
    q = rng.normal(size=(B, H, 1, Dh)).astype(np.float32)
    k = rng.normal(size=(B, KV, T, Dh)).astype(np.float32)
    v = rng.normal(size=(B, KV, T, Dh)).astype(np.float32)
    k_new = rng.normal(size=(B, KV, 1, Dh)).astype(np.float32)
    v_new = rng.normal(size=(B, KV, 1, Dh)).astype(np.float32)
    lens = np.asarray(lens)
    wp = np.maximum(lens - 1, 0)
    pk, pv, pkn, pvn = (model._packed_rows(jnp.asarray(a))
                        for a in (k, v, k_new, v_new))
    assert pk.shape == (B, 2, T, 128)
    o, nk, nv = ragged_decode_attention(
        model._packed_queries(jnp.asarray(q)), pk, pv,
        jnp.asarray(lens, jnp.int32), pkn, pvn, jnp.asarray(wp, jnp.int32),
        interpret=True)
    o = np.asarray(model._own_part(o))
    # plain attention, a head at a time, the new row in its place first
    for b in range(B):
        if lens[b] == 0:
            assert not o[b].any()
            np.testing.assert_array_equal(np.asarray(nk[b]), np.asarray(pk[b]))
            continue
        kb, vb = k[b].copy(), v[b].copy()
        kb[:, wp[b]], vb[:, wp[b]] = k_new[b, :, 0], v_new[b, :, 0]
        for h in range(H):
            s = q[b, h, 0] @ kb[h // 2, :lens[b]].T / 8.0
            p = np.exp(s - s.max())
            want = (p / p.sum()) @ vb[h // 2, :lens[b]]
            np.testing.assert_allclose(o[b, h, 0], want, atol=2e-5)
        np.testing.assert_array_equal(
            np.asarray(nk[b]), np.asarray(model._packed_rows(
                jnp.asarray(kb[None])))[0])
    # the dots over the same rows, as a CPU's step takes them
    live = lens > 0
    dots = cache_attention(
        model._packed_queries(jnp.asarray(q)), nk, nv,
        jnp.asarray(wp, jnp.int32), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(model._own_part(dots))[live], o[live], atol=2e-5)


@pytest.mark.parametrize("activation", ["silu", None])
@pytest.mark.parametrize("taps", [3, 4])
def test_the_shared_convolution_is_a_plain_loop(activation, taps):
    """``conv_prefill`` / ``conv_step``, which the qwen3_next block calls
    with SiLU and this one without, against a loop over positions and
    taps; the tail at each sequence's own length."""
    rng = np.random.default_rng(taps)
    B, T, C = 3, 12, 8
    x = rng.normal(size=(B, T + 1, C)).astype(np.float32)
    w = rng.normal(size=(taps, C)).astype(np.float32)
    lens = np.array([1, 7, 12])
    act = (lambda y: y / (1 + np.exp(-y))) if activation else (lambda y: y)
    want = np.zeros((B, T + 1, C), np.float32)
    for t in range(T + 1):
        for j in range(taps):
            s = t - (taps - 1) + j
            if s >= 0:
                want[:, t] += w[j] * x[:, s]
    y, tail = gated_delta.conv_prefill(
        jnp.asarray(x[:, :T]), jnp.asarray(w), jnp.asarray(lens),
        activation=activation)
    np.testing.assert_allclose(y, act(want[:, :T]), atol=1e-5)
    for b, n in enumerate(lens):
        rows = np.concatenate([np.zeros((taps, C), np.float32), x[b, :n]])
        np.testing.assert_array_equal(tail[b], rows[-(taps - 1):])
    # one more token of each sequence from its tail; sequence 1 idle
    live = np.array([True, False, True])
    at = np.minimum(lens, T)
    y1, new = gated_delta.conv_step(
        jnp.asarray(x[np.arange(B), at]), tail, jnp.asarray(w),
        jnp.asarray(live), activation=activation)
    for b in (0, 2):
        seq = np.concatenate([x[b, :lens[b]], x[b, at[b]][None]])
        full = sum(w[j] * np.concatenate(
            [np.zeros((taps - 1, C), np.float32), seq])[len(seq) - 1 + j]
            for j in range(taps))
        np.testing.assert_allclose(y1[b], act(full), atol=1e-5)
    np.testing.assert_array_equal(new[1], tail[1])
    with pytest.raises(ValueError):
        gated_delta.conv_step(jnp.asarray(x[:, 0]), tail, jnp.asarray(w),
                              jnp.asarray(live), activation="gelu")


def test_a_bias_that_changes_a_pick_changes_no_weight():
    """``expert_bias`` enters the selection only: under a bias wide enough
    to change picks the weights are still the picked experts' plain scores
    over their sum, and where the picks stay the weights stay bit for
    bit."""
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.normal(size=(64, 32)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(32, 16)) / np.sqrt(32), jnp.float32)
    bias = jnp.asarray(rng.normal(size=(16,)) * 0.03, jnp.float32)
    plain_picks, plain_w = experts.route(x, router, None, 4, 1.0, "sigmoid")
    picks, w = experts.route(x, router, bias, 4, 1.0, "sigmoid")
    moved = np.any(np.sort(picks, -1) != np.sort(plain_picks, -1), -1)
    assert 0.1 < moved.mean() < 0.9
    s = np.asarray(jax.nn.sigmoid(x @ router))
    sel = np.take_along_axis(s, np.asarray(picks), -1)
    np.testing.assert_allclose(w, sel / sel.sum(-1, keepdims=True), rtol=1e-6)
    same = ~moved & np.all(np.asarray(picks) == np.asarray(plain_picks), -1)
    assert same.any()
    np.testing.assert_array_equal(np.asarray(w)[same], np.asarray(plain_w)[same])


def test_the_four_shares_of_four_experts_are_the_uncut_layer(served):
    """The share test: a layer's 16 experts divided over four chips. Each
    share routes over all 16 (router and bias whole) and computes its own
    4; the four parts add up to what the reference gives for the whole
    layer, prefill (grouped) and decode (touched) alike. No shared expert:
    nothing is counted once."""
    whole = DecoderLM(**dict(SMALL, experts_held=None))
    params = whole.init_params(5)
    p = params["layers"][2]
    rng = np.random.default_rng(6)
    h = jnp.asarray(rng.normal(size=(2, 24, 128)), jnp.float32)
    m = reference._norm(h.reshape(-1, 128), p["ln_ffn"], 1e-5)
    with jax.default_matmul_precision("highest"):
        want, own, _scores, _w = reference._routed_ffn(m, p, whole.cfg, None, "")
    for live in (None, jnp.ones((2,), bool)):
        rows = h if live is None else h[:, :1]
        total = jnp.zeros_like(rows)
        for share in range(4):
            model = DecoderLM(**dict(SMALL, experts_held=(4 * share, 4)))
            mine = dict(p, **{n: p[n][4 * share:4 * share + 4]
                              for n in ("we1", "we3", "we2")})
            out, picks, _counts = model._ffn(mine, rows, True, live=live)
            total = total + (out - rows)
            want_picks = np.asarray(own).reshape(2, 24, 4)[:, :rows.shape[1]]
            assert np.array_equal(np.sort(picks, -1), np.sort(want_picks, -1))
        got = np.asarray(want).reshape(2, 24, 128)[:, :rows.shape[1]]
        np.testing.assert_allclose(total, got, atol=2e-5)


def test_padding_is_sent_to_no_expert_of_a_share(served):
    """ROADMAP R0 vii: the pad rows of a bucket route together; a share
    sends them to no expert, so they cost no room and move nothing."""
    model, params = served
    prompt = jnp.zeros((2, 128), jnp.int32)
    few = model.prefill_counted(params, prompt, 128, jnp.asarray([3, 9]))[2]
    all_ = model.prefill_counted(params, prompt, 128, jnp.asarray([127, 127]))[2]
    names = model.prefill_counter_names
    few, all_ = (dict(zip(names, np.asarray(c).tolist())) for c in (few, all_))
    # every padded row routes 4 picks in 5 expert layers either way
    assert few["moe_prefill_pairs_routed"] == 2 * 128 * 4 * 5
    assert all_["moe_prefill_pairs_routed"] == 2 * 128 * 4 * 5
    assert few["moe_prefill_pairs_moved"] <= all_["moe_prefill_pairs_moved"]


@pytest.mark.parametrize("setting", [
    {"prefix_cache_hbm_bytes": 1 << 20}, {"prefill_chunk": 64},
    {"hbm_ledger_bytes": 1 << 30}, {"host_kv_tier_bytes": 1 << 20},
    {"swap_drain_ms": 100}])
def test_what_needs_the_tails_carried_is_refused_at_load(served, setting):
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
                 lambda: model.decode_block_cache(params, {}, None, None),
                 lambda: model.loss_fn(params, None),
                 lambda: model.param_sharding(None, params)):
        with pytest.raises(UnsupportedByModel):
            call()
    with pytest.raises(ValueError):
        DecoderLM(**dict(SMALL, layer_types=KINDS[:5]))
    with pytest.raises(ValueError):
        DecoderLM(**dict(SMALL, layer_types=["linear_attention"] * 6))
    with pytest.raises(ValueError):
        DecoderLM(**dict(SMALL, experts_held=(14, 4)))
    with pytest.raises(ValueError):
        DecoderLM(**dict(SMALL, conv_kernel=1))
