"""The afmoe block (``models/afmoe.py``) against its plain reference
(``benchmark/reference/afmoe.py``), its kernels against their dots, and the
paths that refuse it. CPU, small: hidden 256, 4 heads / 1 KV head of 128, 8
experts top-2 + 1 shared, window 256, 1 dense + 4 expert layers (s, s, s,
f), vocabulary 1024; seeded random float32 weights, logits not tokens."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.reference import afmoe as reference
from seldon_core_tpu.models.family import DecoderFamily
from seldon_core_tpu.models.llm import DecoderLM, UnsupportedByModel
from seldon_core_tpu.ops import experts
from seldon_core_tpu.ops.decode_attention import (
    cache_attention, cache_write, decode_attention, ragged_decode_attention,
    walk_block)
from seldon_core_tpu.ops.flash_attention import _banded_attention, flash_attention

CFG = dict(
    block="afmoe", vocab_size=1024, d_model=256, n_layers=5, n_heads=4,
    n_kv_heads=1, head_dim=128, d_ff=512, max_seq=1024, rope_theta=1e4,
    dtype="float32", sliding_window=256, n_dense_layers=1, n_routed_experts=8,
    layer_types=["sliding_attention"] * 4 + ["full_attention"],
    experts_per_tok=2, expert_width=128, n_shared_experts=1, route_scale=2.826,
    residual_scale=0.3)
T = 384          # past the window
TIGHT = 1e-4     # float32 against float32: max |diff| / std of the logits


@pytest.fixture(scope="module")
def served():
    model = DecoderLM(**CFG)
    params = model.init_params(0)
    # a bias that moves the selection: the control "bias added to the
    # weights" must then differ
    for p in params["layers"]:
        if "expert_bias" in p:
            p["expert_bias"] = jnp.linspace(-0.2, 0.2, 8)
    tokens = np.random.default_rng(0).integers(0, 1024, T)
    ref, picks, _ = reference.forward(params, model.cfg, tokens, list(range(T)))
    return model, params, tokens, ref, picks


def _err(got, ref, scale):
    return float(np.abs(np.asarray(got) - ref).max() / scale)


def test_the_block_variant_builds_the_class_and_knows_its_kinds(served):
    model = served[0]
    assert type(model).__name__ == "AfmoeLM" and isinstance(model, DecoderFamily)
    assert sorted(model.attention_kinds(), key=str) == [(1, None), (4, 256)]
    assert DecoderLM(d_model=256, n_heads=2).attention_kinds() == ((8, None),)
    assert model.cfg.head_dim == 128 != model.cfg.d_model // model.cfg.n_heads
    assert DecoderLM(d_model=256, n_heads=2).cfg.head_dim == 128
    leaves = jax.tree_util.tree_leaves(served[1])
    assert model.n_params() == sum(a.size for a in leaves)
    ok, why = DecoderLM.params_swappable(served[1], model.init_params(1))
    assert ok, why
    # a burst is priced by its live lanes: fewer experts touched, fewer buckets
    read = lambda **kw: model.dispatch_read_bytes(  # noqa: E731
        "decode_burst", rows=4, k=1, bucket=128, **kw)
    assert read(live=1) < read(live=4) == read()
    with pytest.raises(ValueError):
        DecoderLM(block="nope")
    with pytest.raises(ValueError):
        DecoderLM(**dict(CFG, layer_types=["full_attention"] * 4))


def test_prefill_and_full_forward_agree_with_the_reference(served):
    model, params, tokens, ref, picks = served
    full = model.apply(params, jnp.asarray(tokens[None], jnp.int32))[0]
    assert _err(full, ref, ref.std()) < TIGHT
    got = model._prefill(params, jnp.asarray(tokens[None], jnp.int32), T)[2]
    assert len(got) == 4 and all((np.sort(a[0], -1) == np.sort(b, -1)).all()
               for a, b in zip(got, picks))


@pytest.mark.parametrize("variant", [
    "no_window", "rope_on_full", "no_gate", "bias_in_weights"])
def test_a_wrong_model_does_not_agree(served, variant):
    """Controls that must fail: window off, rotary on the full layer, gate
    off, the bias added to the weights."""
    model, params, tokens, ref, _ = served
    wrong = reference.forward(params, model.cfg, tokens, list(range(T)),
                              variant)[0]
    full = model.apply(params, jnp.asarray(tokens[None], jnp.int32))[0]
    assert _err(full, wrong, ref.std()) > 0.05


def test_prefill_then_decode_through_the_cache_past_the_window(served):
    model, params, tokens, ref, _ = served
    start = 200      # decode from inside the window to 128 past it
    logits, cache = model.prefill(
        params, jnp.asarray(tokens[None, :start], jnp.int32), 512)
    assert _err(logits[0], ref[start - 1], ref.std()) < TIGHT
    # lane 1 of 3 live: the others idle (lens 0), left out of the counts
    ks = [jnp.zeros((3,) + cache["k"][l].shape[1:]).at[1].set(cache["k"][l][0])
          for l in range(5)]
    vs = [jnp.zeros((3,) + cache["v"][l].shape[1:]).at[1].set(cache["v"][l][0])
          for l in range(5)]
    live = np.arange(3) == 1
    step = jax.jit(lambda ks, vs, tok, pos, lens: model.decode_step_ragged_list(
        params, ks, vs, tok, pos, lens=lens))
    worst = 0.0
    for pos in range(start, T):
        logits, ks, vs, counts = step(
            ks, vs, jnp.asarray(np.where(live, tokens[pos], 0)[:, None], jnp.int32),
            jnp.asarray(np.where(live, pos, 0), jnp.int32),
            jnp.asarray(np.where(live, pos + 1, 0), jnp.int32))
        worst = max(worst, _err(logits[1], ref[pos], ref.std()))
        assert counts.tolist() == [8, 8, 4]   # 2 picks x 4 layers, one lane
    assert worst < TIGHT
    assert model.step_counter_names == (
        "moe_experts_touched", "moe_rows_routed", "moe_layer_steps")


def test_chunked_prefill_across_the_windows_edge(served):
    model, params, tokens, ref, _ = served
    slab = {n: jnp.zeros((5, 1, 1, 512, 128)) for n in ("k", "v")}
    for start in range(0, T, 128):      # the third chunk lies past the window
        logits, slab = model.prefill_chunk(
            params, slab, jnp.asarray(tokens[None, start:start + 128], jnp.int32),
            start, 512, want_logits=start + 128 == T)
    assert _err(logits[0], ref[T - 1], ref.std()) < TIGHT


def test_prefix_prefill_and_the_decode_window(served):
    model, params, tokens, ref, _ = served
    _, cache = model.prefill(params, jnp.asarray(tokens[None, :300], jnp.int32), 512)
    prefix = {n: cache[n][:, :, :, :256] for n in ("k", "v")}
    logits, suffix = model.prefill_with_prefix(
        params, prefix, jnp.asarray(tokens[None, 200:328], jnp.int32), 200)
    assert _err(logits[0], ref[327], ref.std()) < TIGHT
    assert suffix["k"].shape == (5, 1, 1, 128, 128)
    logits, _, _ = model.decode_chunk_ragged_list(
        params, [cache["k"][l] for l in range(5)],
        [cache["v"][l] for l in range(5)],
        jnp.asarray(tokens[None, 300:304], jnp.int32), jnp.asarray([300], jnp.int32))
    assert _err(logits[0], ref[300:304], ref.std()) < TIGHT


# -- the kernels ------------------------------------------------------------------

def _kernel_case(seed, lens, window, lanes=5, heads=8, kv=1, t=1024):
    rng = np.random.default_rng(seed)
    mk = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.bfloat16)  # noqa: E731
    q, k, v = mk(lanes, heads, 1, 128), mk(lanes, kv, t, 128), mk(lanes, kv, t, 128)
    k_new, v_new = mk(lanes, kv, 1, 128), mk(lanes, kv, 1, 128)
    lens = jnp.asarray(lens, jnp.int32)
    wp = jnp.where(lens > 0, lens - 1, t)       # idle lanes park their write
    return q, k, v, k_new, v_new, lens, wp, jnp.maximum(0, lens - window)


@pytest.mark.parametrize("block", [None, 256, 128], ids=["rule", "256", "128"])
@pytest.mark.parametrize("seed, lens, window", [
    (0, [700, 0, 1024, 130, 257], 256),     # starts inside, at and off a block
    (1, [256, 255, 1, 0, 640], 256),        # window not yet full; one key
    (2, [1024, 1000, 900, 513, 385], 384),  # a window of three blocks
])
def test_the_ragged_kernel_with_starts_against_the_masked_dots(
        seed, lens, window, block):
    """Interpreted, as PR 30's tests run the kernel, under the block the
    rule gives the call (1,024, this whole cache: one KV head of 128 in
    bfloat16), under the cell's 256 and under 128: the caches
    bit for bit the scatter's, the read the masked dots' to bfloat16
    rounding, and a lane's blocks left of its window never asked for."""
    q, k, v, k_new, v_new, lens, wp, starts = _kernel_case(seed, lens, window)
    walked = block or walk_block(k.shape[1], k.shape[3], k.dtype, k.shape[2])
    assert walked == (block or 1024)
    o, k2, v2 = ragged_decode_attention(
        q, k, v, lens, k_new, v_new, wp, interpret=True, starts=starts,
        block=block)
    kr, vr = cache_write(k, k_new, wp[:, None]), cache_write(v, v_new, wp[:, None])
    assert bool((k2 == kr).all()) and bool((v2 == vr).all())
    want = cache_attention(q, kr, vr, lens - 1, q.dtype, lo=starts)
    live = np.asarray(lens) > 0
    diff = np.abs(np.asarray(o, np.float32) - np.asarray(want, np.float32))
    assert diff[live].max() <= 0.0079       # two bfloat16 steps at |o| < 1
    assert not np.asarray(o, np.float32)[~live].any()
    # poison what lies left of each window's first block: nothing changes
    col = jnp.arange(k.shape[2])[None, None, :, None]
    left = col < (starts // walked * walked)[:, None, None, None]
    o3, _, _ = ragged_decode_attention(
        q, jnp.where(left, jnp.nan, k), jnp.where(left, jnp.nan, v), lens,
        k_new, v_new, wp, interpret=True, starts=starts, block=block)
    assert bool((o3 == o).all())
    # and the dispatcher's dots take the same band (the CPU's path)
    o4, k4, _ = decode_attention(q, k, v, k_new, v_new, wp, lens - 1, lens,
                                 starts=starts)
    assert bool((k4 == kr).all())
    assert np.abs(np.asarray(o4, np.float32)
                  - np.asarray(want, np.float32))[live].max() == 0.0


def test_without_starts_the_kernel_is_the_one_it_was():
    q, k, v, k_new, v_new, lens, wp, _ = _kernel_case(3, [700, 0, 1024, 130, 257], 256)
    a = ragged_decode_attention(q, k, v, lens, k_new, v_new, wp, interpret=True)
    b = ragged_decode_attention(q, k, v, lens, k_new, v_new, wp, interpret=True,
                                starts=jnp.zeros_like(lens))
    assert all(bool((x == y).all()) for x, y in zip(a, b))


@pytest.mark.parametrize("t, window, block", [(512, 256, 128), (768, 200, 256)])
def test_the_flash_kernel_with_a_window_against_the_banded_dots(t, window, block):
    rng = np.random.default_rng(t)
    q, k, v = (jnp.asarray(rng.standard_normal((1, 2, t, 128)), jnp.float32)
               for _ in range(3))
    got = flash_attention(q, k, v, block_q=block, block_k=block, interpret=True,
                          window=window)
    want = _banded_attention(q, k, v, window)
    assert float(jnp.abs(got - want).max()) < 2e-5
    full = flash_attention(q, k, v, block_q=block, block_k=block, interpret=True)
    assert float(jnp.abs(full - want).max()) > 1e-2      # the band matters
    # key blocks wholly left of the band are not read
    poisoned = k.at[:, :, :block].set(jnp.nan)
    again = flash_attention(q, poisoned, v, block_q=block, block_k=block,
                            interpret=True, window=window)
    rows = slice(-block, None) if t - block - window + 1 >= block else slice(0, 0)
    assert bool(jnp.isfinite(again[:, :, rows]).all())


def _expert_case(n=48, d=256, f=128, e=8, k=2):
    rng = np.random.default_rng(5)
    mk = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)  # noqa: E731
    x, router = mk(n, d), mk(d, e) / 16
    w1, w3, w2 = mk(e, d, f) / 16, mk(e, d, f) / 16, mk(e, f, d) / 11
    picks, weights = experts.route(x, router, jnp.zeros(e), k, 2.826)
    loop = np.zeros((n, d), np.float32)
    for row in range(n):
        for j in range(k):
            ex = int(picks[row, j])
            loop[row] += float(weights[row, j]) * np.asarray(
                (jax.nn.silu(x[row] @ w1[ex]) * (x[row] @ w3[ex])) @ w2[ex])
    return x, picks, weights, (w1, w3, w2), loop


def test_decode_experts_against_the_grouped_path_against_a_plain_loop():
    x, picks, weights, stacks, loop = _expert_case()
    assert np.allclose(np.asarray(weights.sum(-1)), 2.826, atol=1e-5)
    grouped, counts = experts.grouped_experts(x, picks, weights, *stacks)
    in_groups, _ = experts.grouped_experts(x, picks, weights, *stacks,
                                           group_rows=16)
    # every pair moved; off the kernel's shapes the dots work every row
    assert counts.tolist() == [picks.size, picks.size]
    assert np.abs(np.asarray(grouped) - loop).max() < 1e-4
    assert np.abs(np.asarray(in_groups) - loop).max() < 1e-4
    live = jnp.asarray(np.random.default_rng(6).random(48) < 0.3)
    ids, n = experts.touched_experts(picks, live, 8)
    want_ids = sorted(set(np.asarray(picks)[np.asarray(live)].ravel().tolist()))
    assert ids[:int(n)].tolist() == want_ids
    kernel = experts.touched_experts_ffn(
        x, picks, jnp.where(live[:, None], weights, 0.0), ids, n, *stacks,
        tf=64, interpret=True)
    masked = loop * np.asarray(live)[:, None]
    assert np.abs(np.asarray(kernel) - masked).max() < 1e-4
    out, touched, routed = experts.decode_experts(x, picks, weights, live, *stacks)
    assert np.abs(np.asarray(out) - masked).max() < 1e-4
    # idle lanes are left out of both counts
    assert int(touched) == len(want_ids) and int(routed) == 2 * int(live.sum())
    none, touched, routed = experts.decode_experts(
        x, picks, weights, jnp.zeros(48, bool), *stacks)
    assert not np.asarray(none).any() and int(touched) == 0 == int(routed)


def _tile_rows(picks, tm):
    """What the grouped kernel works for these (row, pick) pairs in tiles
    of ``tm`` rows: the pairs sorted by expert lie group after group, and
    a group is worked in every tile that holds a row of it."""
    sizes = np.bincount(np.asarray(picks).ravel(), minlength=8)
    ends = np.cumsum(sizes)
    return tm * sum(-(-int(e) // tm) - int(e - n) // tm
                    for e, n in zip(ends, sizes) if n)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_a_prompts_pad_rows_go_to_no_expert_and_the_prefill_counts_it(served, dtype):
    """80 tokens in a bucket of 256 (the cell's 2048 in 4096, cut small),
    the pad rows' picks sent to no expert against the pad rows routed: the
    same logits at ``last_index``, the same cache rows up to the prompt's
    length and the same served picks. ``prefill_counted`` counts the
    bucket's pairs as routed and moved, and as tile rows what the real
    rows' picks say where the shapes are the kernel's (bfloat16), every row
    where they are the dots'."""
    _, params, tokens, _, _ = served
    model = DecoderLM(**dict(CFG, dtype=dtype))
    n, bucket = 80, 256
    prompt = jnp.asarray(tokens[:bucket], jnp.int32)[None]
    prompt = prompt.at[:, n:].set(0)
    last = jnp.asarray([n - 1], jnp.int32)
    logits, cache, picked = model._prefill(params, prompt, bucket, last)
    x, ks, vs, routed_picks, routed_counts = model._forward(
        params, prompt, bucket, None)
    routed_logits = model._head(params, x, last)
    tol = dict(rtol=0, atol=1e-5 if dtype == "float32" else 2.0 ** -6)
    np.testing.assert_allclose(logits, routed_logits, **tol)
    for leaf, rows in (("k", ks), ("v", vs)):
        np.testing.assert_allclose(
            np.asarray(cache[leaf][:, :, :, :n], np.float32),
            np.asarray(jnp.stack(rows)[:, :, :, :n], np.float32), **tol)
    assert len(picked) == 4
    for mine, theirs in zip(picked, routed_picks):
        np.testing.assert_array_equal(mine[:, :n], theirs[:, :n])
    counted, cache2, counts = model.prefill_counted(params, prompt, bucket, last)
    np.testing.assert_array_equal(counted, logits)
    np.testing.assert_array_equal(cache2["k"], cache["k"])
    pairs = 4 * bucket * 2
    in_kernel = experts.groups_in_kernel(
        "tpu", (bucket * 2, 256), (8, 256, 128), dtype)
    assert in_kernel == (dtype == "bfloat16")
    if in_kernel:
        assert experts.row_tile(bucket * 2) == 128
        worked = sum(_tile_rows(p[0, :n], 128) for p in picked)
        whole = sum(_tile_rows(p[0], 128) for p in routed_picks)
        assert worked < whole
    else:
        worked = whole = pairs
    assert counts.tolist() == [pairs, pairs, worked]
    assert routed_counts.tolist() == [pairs, pairs, whole]


# -- the scheduler, and what refuses the family ---------------------------------------

def test_the_batcher_serves_it_and_counts_what_the_experts_and_windows_do(served):
    from seldon_core_tpu.serving.continuous import ContinuousBatcher

    model, params, tokens, _, _ = served
    prompt = tokens[:300].tolist()
    batcher = ContinuousBatcher(model, params, slots=4, max_seq=1024,
                                prefill_buckets=(32, 128, 512))
    try:
        batcher.start()
        out = batcher.generate(prompt, max_new_tokens=12)
    finally:
        batcher.close()
    # greedy by the reference, teacher-forced with what was served
    want = reference.logits(params, model.cfg, np.asarray(out),
                            list(range(299, 311))).argmax(-1)
    assert len(out) == 312 and out[300:] == want.tolist()
    s = batcher.stats
    # the prefill's counters (PR 43) came home beside a burst: 300 tokens
    # in the 512 bucket, 2 picks, 4 expert layers; every pair of the bucket
    # is moved (all experts are held) and in float32 the rows are the
    # dots', which work every row they are given
    assert batcher._prefill_counters == model.prefill_counter_names == (
        "moe_prefill_pairs_moved", "moe_prefill_pairs_routed",
        "moe_prefill_tile_rows")
    assert [s[name] for name in batcher._prefill_counters] == [4 * 512 * 2] * 3
    assert s["moe_layer_steps"] == 4 * s["steps"] > 0
    assert s["moe_experts_touched"] == 2 * s["moe_layer_steps"]    # one lane
    assert s["moe_rows_routed"] == s["moe_experts_touched"]
    assert 0 < s["kv_positions_seen_window"] < s["kv_positions_live_window"]
    assert s["kv_positions_seen_window"] <= s["kv_positions_read_window"]


def test_a_dense_batcher_counts_no_window_and_returns_what_it_did():
    from seldon_core_tpu.serving.continuous import (
        ContinuousBatcher, _positions_windowed)

    model = DecoderLM(vocab_size=256, d_model=128, n_layers=2, n_heads=2,
                      n_kv_heads=1, d_ff=256, max_seq=256, dtype="float32")
    batcher = ContinuousBatcher(model, model.init_params(0), slots=2, max_seq=256)
    try:
        assert batcher._step_counters == () and batcher._kv_windows == ()
        out = batcher._burst_fn(
            batcher.params, batcher._cache, batcher._cur_tok, batcher._pos,
            jnp.zeros(2, bool), jnp.zeros(2), batcher._keys, 2, None)
        assert len(out) == 5      # toks, cur_tok, pos, cache, keys: as before
        # the burst's modelled read is the model's own: every weight and
        # every row's bucket, whatever lanes are live
        assert model.dispatch_read_bytes(
            "decode_burst", rows=2, live=1, k=8, bucket=128,
            param_bytes=batcher._param_bytes,
            kv_row_bytes=batcher._kv_key_bytes) == 8 * (
                batcher._param_bytes + 2 * 128 * batcher._kv_key_bytes)
    finally:
        batcher.close()
    # 3 steps from position 300 under a window of 256, blocks of 128: each
    # reads blocks 0-2 (the window starts in block 0), sees 256, holds 301-303
    assert _positions_windowed(300, 3, 256, 128) == (3 * 384, 3 * 256, 301 + 302 + 303)
    assert _positions_windowed(500, 1, 256, 128) == (384, 256, 501)
    # blocks of 256: both edges of the window round twice as far
    assert _positions_windowed(300, 3, 256, 256) == (3 * 512, 3 * 256, 301 + 302 + 303)
    assert _positions_windowed(500, 1, 256, 256) == (512, 256, 501)


@pytest.fixture(scope="module")
def windowed_at_4_kv_heads():
    """A batcher over 4 KV heads of 128 in bfloat16 (the cells' shape), one
    window layer and one full one."""
    from seldon_core_tpu.serving.continuous import ContinuousBatcher

    model = DecoderLM(**dict(
        CFG, n_kv_heads=4, dtype="bfloat16", n_layers=2, n_dense_layers=1,
        layer_types=["sliding_attention", "full_attention"]))
    batcher = ContinuousBatcher(model, model.init_params(0), slots=2,
                                max_seq=1024, prefill_buckets=(128,),
                                steps_per_poll=4)
    batcher.start()
    yield batcher
    batcher.close()


@pytest.mark.parametrize("prompt_len,read,window_read,seen,live", [
    # one burst of 4 steps from position p reads p + 1 .. p + 4 keys, in
    # blocks of 256, a window of 256. Inside the window (101-104 keys): block
    # 0 in either kind of layer, every key seen
    (100, 4 * 256, 4 * 256, 410, 410),
    # one block past it (601-604 keys): a full layer walks blocks 0-2; the
    # window starts at 345-348, in block 1, so blocks 1-2 for 256 keys seen
    (600, 4 * 768, 4 * 512, 4 * 256, 2410),
    # a request that ends at max_seq (1020-1023 keys): all four blocks; the
    # window starts at 764-767, in block 2
    (1019, 4 * 1024, 4 * 512, 4 * 256, 4086),
])
def test_a_windowed_batcher_at_4_kv_heads_counts_the_walk_of_256(
        windowed_at_4_kv_heads, prompt_len, read, window_read, seen, live):
    """``kv_positions_read`` and the window's three counters are what the
    kernel streams at the block its rule gives the cache, counted by hand:
    ``kv_window_read_share`` is their ratio."""
    batcher = windowed_at_4_kv_heads
    assert batcher._kv_read_block == 256 and batcher._kv_windows == (256,)
    names = ("steps", "kv_positions_read", "kv_positions_read_window",
             "kv_positions_seen_window", "kv_positions_live_window")
    before = [batcher.stats[name] for name in names]
    prompt = np.random.default_rng(prompt_len).integers(0, 1024, prompt_len)
    out = batcher.generate(prompt.tolist(), max_new_tokens=5)
    assert len(out) == prompt_len + 5
    assert [batcher.stats[name] - was for name, was in zip(names, before)] == [
        4, read, window_read, seen, live]


@pytest.mark.parametrize("asked", [
    {"mesh": True}, {"host_kv_tier_bytes": 1 << 20}, {"draft": True}])
def test_paths_without_a_path_for_the_family_refuse_it_at_load(served, asked):
    from seldon_core_tpu.serving.continuous import ContinuousBatcher

    model, params = served[0], served[1]
    kw = {}
    if asked.get("mesh"):
        kw["mesh"] = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("model",))
    if asked.get("draft"):
        kw.update(draft_model=model, draft_params=params, speculate_tokens=2)
    if "host_kv_tier_bytes" in asked:
        kw["host_kv_tier_bytes"] = asked["host_kv_tier_bytes"]
    with pytest.raises(UnsupportedByModel):
        ContinuousBatcher(model, params, slots=2, max_seq=256, **kw)
    for call in (lambda: model.loss_fn(params, None),
                 lambda: model.decode_step(params, None, jnp.zeros((1, 1), jnp.int32), 0),
                 lambda: model.param_sharding(None, params)):
        with pytest.raises(UnsupportedByModel):
            call()
