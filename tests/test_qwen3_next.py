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
    # the prefills' counters came home with the bursts: every padded token
    # routed 4 picks in 8 layers, and the grouped path moved a room of
    # them a call (384 of a 128-bucket prompt's 512, and of two prompts'
    # 1024), never the fall-back's second pass
    routed = stats["moe_prefill_pairs_routed"]
    assert routed == stats["prefill_tokens"] * 4 * 8 > 0
    assert 0.375 * routed <= stats["moe_prefill_pairs_moved"] <= 0.75 * routed
    assert stats["moe_prefill_pairs_moved"] % experts.ROOM_TILE == 0
    # and the chunks of the gated delta rule, in 6 linear layers: what each
    # prompt has, and what its bucket has (32, 128, 128, 128, 256, 32: a
    # bucket under a chunk is padded to one)
    assert stats["gdn_prefill_chunks_walked"] == 6 * (1 + 2 + 2 + 1 + 3 + 1)
    assert stats["gdn_prefill_chunks_bucket"] == 6 * (1 + 2 + 2 + 2 + 4 + 1)


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
        experts.grouped_experts(x, picks, w, w1, w1, w2, held=(8, 8),
                                n_routed=32)[0][:12],
        want[:12], atol=1e-4)
    with pytest.raises(ValueError):     # a share of how many
        experts.grouped_experts(x, picks, w, w1, w1, w2, held=(8, 8))


def _parent_grouped(x, picks, weights, w1, w3, w2, dropped=False):
    """``ops.experts._grouped`` as the parent of PR 39 had it, verbatim:
    every (row, pick) pair sorted, gathered, multiplied, weighted,
    unsorted and summed. What a share's room must equal."""
    n, d = x.shape
    k = picks.shape[1]
    n_experts = w1.shape[0]
    flat = picks.reshape(-1)
    order = jnp.argsort(flat, stable=True)
    xs = x[order // k]
    sizes = jnp.sum(
        flat[:, None] == jnp.arange(n_experts, dtype=flat.dtype)[None, :],
        axis=0, dtype=jnp.int32)
    a = lax.ragged_dot(xs, w1, sizes, preferred_element_type=jnp.float32)
    g = lax.ragged_dot(xs, w3, sizes, preferred_element_type=jnp.float32)
    h = (jax.nn.silu(a) * g).astype(x.dtype)
    y = lax.ragged_dot(h, w2, sizes, preferred_element_type=jnp.float32)
    by_expert = weights.reshape(-1)[order][:, None]
    if dropped:
        y = jnp.where(flat[order][:, None] < n_experts, y, 0.0)
    y = (y * by_expert).astype(x.dtype)
    back = jnp.argsort(order)
    return y[back].reshape(n, k, d).astype(jnp.float32).sum(axis=1)


def _parent_held(x, picks, weights, stacks, held):
    local, kept = experts.localise(picks, weights, held, stacks[0].shape[0])
    return _parent_grouped(x, local, kept, *stacks, True)


def _share_case(rows=96, seed=11, dtype=jnp.float32):
    """Seeded rows routed over the share test's 16 experts, top 4, and
    the four shares' stacks of 4."""
    rng = np.random.default_rng(seed)
    mk = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
    x, router = mk(rows, 128), mk(128, 16) / 8
    picks, weights = experts.route(x, router, None, 4, 1.0, score="softmax")
    stacks = [tuple(a.astype(dtype) for a in (
        mk(4, 128, 64) / 11, mk(4, 128, 64) / 11, mk(4, 64, 128) / 8))
        for _ in range(4)]
    return x.astype(dtype), picks, weights, stacks


def _one_rounding(want):
    """The sum over a row's picks keeps float32 and the picks' products,
    in another order: each of the 4 additions may round once apart."""
    return 4 * float(np.abs(want).max()) * 2.0 ** -23


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("share", range(4))
def test_a_share_over_its_room_is_the_parents_grouped_path(share, dtype):
    """At the four shares of the share test: 384 pairs, about 96 land on a
    share, its room is 128 and one pass moves them; the result is the
    parent's over all 384, to the order of a float32 sum of 4 terms."""
    x, picks, weights, stacks = _share_case(dtype=dtype)
    held = (4 * share, 4)
    assert experts.room_of(384, held, 16) == 128
    want = np.asarray(_parent_held(x, picks, weights, stacks[share], held))
    got, (moved, worked) = experts.grouped_experts(
        x, picks, weights, *stacks[share], held=held, n_routed=16)
    assert got.dtype == jnp.float32 and int(moved) == 128 == int(worked)
    np.testing.assert_allclose(got, want, rtol=0, atol=_one_rounding(want))
    # a capture names the share's ops by their scope
    assert "held_experts_prefill" in experts.grouped_experts.lower(
        x, picks, weights, *stacks[share], held=held,
        n_routed=16).as_text(debug_info=True)


@pytest.mark.parametrize("skew", ["every_pick_here", "three_times_the_share"])
def test_the_room_falls_back_drop_free(skew):
    """A router that sends a share more than its room: every pair that
    landed is still computed, in passes over the same code, and the count
    of pairs moved says that the fall-back ran. Equal to ``held=None``
    over the cut stacks, which drops nothing by construction."""
    x, picks, weights, stacks = _share_case()
    held = (8, 4)
    rng = np.random.default_rng(12)
    here = (jnp.ones(picks.shape, bool) if skew == "every_pick_here"
            else jnp.asarray(rng.random(picks.shape) < 0.75))
    picks = jnp.where(
        here, jnp.asarray(rng.integers(8, 12, size=picks.shape), jnp.int32),
        jnp.asarray(rng.integers(0, 8, size=picks.shape), jnp.int32))
    landed = int(here.sum())
    assert landed > 2 * 128                       # c > room
    want = np.asarray(experts.grouped_experts(
        x, jnp.where(here, picks - 8, 0), jnp.where(here, weights, 0.0),
        *stacks[2])[0])
    got, (moved, _) = experts.grouped_experts(
        x, picks, weights, *stacks[2], held=held, n_routed=16)
    assert int(moved) == -(-landed // 128) * 128 >= 3 * 128
    np.testing.assert_allclose(got, want, rtol=0, atol=_one_rounding(want))
    np.testing.assert_allclose(
        got, _parent_held(x, picks, weights, stacks[2], held), rtol=0,
        atol=_one_rounding(want))


@pytest.mark.parametrize("rows,group_rows,groups", [
    (96, 32, 3), (96, 48, 2), (40, 8, 5), (130, 128, 2)])
def test_more_rows_than_a_group_go_through_the_room_in_groups(
        rows, group_rows, groups):
    """``prefill_many``'s rows: above ``group_rows`` the share's path runs
    group by group (``lax.map``), each group over its own room, also
    where a group's rows are no multiple of anything (65: one band)."""
    x, picks, weights, stacks = _share_case(rows=rows, seed=13)
    held = (12, 4)
    want = np.asarray(_parent_held(x, picks, weights, stacks[3], held))
    got, (moved, _) = experts.grouped_experts(
        x, picks, weights, *stacks[3], group_rows=group_rows, held=held,
        n_routed=16)
    room = experts.room_of(rows // groups * 4, held, 16)
    assert int(moved) % room == 0 and int(moved) >= groups * room
    np.testing.assert_allclose(got, want, rtol=0, atol=_one_rounding(want))


def test_a_room_is_the_shares_expected_pairs_and_a_quarter_more():
    # the cell: 4096 rows x 10 picks, 128 of 512 held: 5/16 of the pairs
    # is 100 tiles of 128, and an odd number of them is 101
    assert experts.room_of(40960, (0, 128), 512) == 12928
    assert experts.room_of(5120, (128, 128), 512) == 1664
    assert experts.room_of(1280, (384, 128), 512) == 640
    # never more than there are, and all of them where all are held
    assert experts.room_of(64, (8, 8), 32) == 64
    assert experts.room_of(40960, (0, 512), 512) == 40960


def test_a_whole_layer_moves_every_pair_and_a_share_its_room(served):
    """``prefill_counted``: ``prefill`` and the counters, for a model
    that holds a share and for one that holds every expert."""
    model, params = served
    whole = DecoderLM(**dict(SMALL, experts_held=None))
    assert model.prefill_counter_names == whole.prefill_counter_names == (
        "moe_prefill_pairs_moved", "moe_prefill_pairs_routed",
        "gdn_prefill_chunks_walked", "gdn_prefill_chunks_bucket",
        "moe_prefill_tile_rows")
    prompt = jnp.asarray(
        np.random.default_rng(2).integers(0, 256, size=(2, 64)), jnp.int32)
    logits, slab = model.prefill(params, prompt, 64)
    counted, slab2, counts = model.prefill_counted(params, prompt, 64)
    np.testing.assert_array_equal(logits, counted)
    for name in slab:
        np.testing.assert_array_equal(slab[name], slab2[name])
    # 128 rows x 4 picks in 8 layers; a layer's room is 384 of its 512;
    # two sequences that fill their one chunk in 6 linear layers; in
    # float32 the rows are the dots', which work every row they are given
    assert counts.tolist() == [8 * 384, 8 * 512, 12, 12, 8 * 384]
    assert whole.prefill_counted(whole.init_params(3), prompt, 64)[2].tolist() == [
        8 * 512, 8 * 512, 12, 12, 8 * 512]


@pytest.mark.parametrize("bucket,lens", [
    (64, [64]), (128, [1]), (128, [64, 65]), (256, [63, 129, 256, 192])],
    ids=["the_whole_bucket", "one_token", "either_side_of_a_chunk", "four_rows"])
def test_a_prefill_counts_the_chunks_its_sequences_have(served, bucket, lens):
    """``gdn_prefill_chunks_walked`` is ``ceil(lens / 64)`` a (sequence,
    linear layer) and ``gdn_prefill_chunks_bucket`` the bucket's chunks,
    from ``last_index`` and the prompt's shape, whatever the padding holds."""
    model, params = served
    prompt = jnp.asarray(np.random.default_rng(5).integers(
        0, 256, size=(len(lens), bucket)), jnp.int32)
    *_, counts = model.prefill_counted(
        params, prompt, 256, jnp.asarray(lens, jnp.int32) - 1)
    assert counts.tolist()[2:4] == [
        6 * sum(-(-n // 64) for n in lens), 6 * len(lens) * bucket // 64]
    assert counts.tolist()[1] == 8 * len(lens) * bucket * 4


@pytest.mark.parametrize("held", [(4, 4), None], ids=["a_share", "every_expert"])
def test_padding_is_sent_to_no_expert_of_a_share(served, held):
    """Rows past a sequence's last token: padding routes together (here
    96 equal rows of 128, whose four picks are made the share's own), and
    would overflow the share's room into further passes. Their picks go
    nowhere, the real rows' results do not move, and one room is moved.
    Where every expert is held nothing is masked."""
    model, params = served
    model = DecoderLM(**dict(SMALL, experts_held=held))
    layer = dict(params["layers"][0])
    if held is None:
        layer.update(DecoderLM(**dict(SMALL, experts_held=None)).init_params(
            3)["layers"][0])
    rng = np.random.default_rng(6)
    h = jnp.asarray(rng.normal(size=(1, 128, 128)), jnp.float32)
    h = h.at[:, 32:].set(h[:, 32])
    # the padding's picks: the router's columns 4..7 are its row, scaled
    m = model._norm(h, layer["ln_post"])[0, 32]
    layer["router"] = layer["router"].at[:, 4:8].set(m[:, None] * 4)
    real = jnp.arange(128)[None, :] < 32
    out, picks, (moved, _) = model._moe(layer, h, real=real)
    plain, picks2, (moved2, _) = model._moe(layer, h)
    np.testing.assert_array_equal(picks, picks2)      # what was routed
    assert sorted(picks[0, 40].tolist()) == [4, 5, 6, 7]
    np.testing.assert_array_equal(out[:, :32], plain[:, :32])
    if held is None:
        np.testing.assert_array_equal(out, plain)
        assert moved == moved2 == 512
    else:
        # 96 x 4 pairs of padding and the real rows' own: three rooms of 384
        assert int(moved2) >= 2 * 384 and int(moved) == 384
        assert float(jnp.abs(out[:, 32:] - plain[:, 32:]).max()) > 1e-3


@pytest.mark.parametrize("counted", [True, False])
def test_an_insert_sums_the_prefill_counters_only_where_a_family_names_them(
        served, batcher, counted):
    """The qwen3_next batcher's inserts take the counters so far and the
    prefill's and return their sum last; called without (as a comparison
    that fills a cache does) they return what they always did. A family
    that names none has no such argument in use."""
    model, params = served
    if counted:
        b = batcher
        assert b._prefill_counters == model.prefill_counter_names
        assert [a.tolist() for a in b._no_prefill_counts] == [[0, 0, 0, 0, 0]]
    else:
        dense = DecoderLM(vocab_size=128, d_model=64, n_layers=2, n_heads=4,
                          n_kv_heads=2, d_ff=128, max_seq=128, dtype="float32")
        b = ContinuousBatcher(dense, dense.init_params(0), slots=4, max_seq=128)
        assert b._prefill_counters == () and b._prefill_counts == []
        assert b._take_prefill_counts() == []
    try:
        prompt = jnp.asarray(
            np.random.default_rng(4).integers(0, 128, size=(1, 128)), jnp.int32)
        first, one, key, *counts = b._prefill_fn(
            b.params, prompt, jnp.asarray([9], jnp.int32), jnp.int32(0),
            jnp.float32(0.0))
        assert len(counts) == int(counted)
        fresh = lambda: b.model.cache_layers(4, b.max_seq)  # noqa: E731
        regs = (jnp.zeros((4,), jnp.int32), jnp.zeros((4,), jnp.int32),
                jnp.zeros((4, 2), jnp.uint32))
        plain = b._insert_fn(fresh(), one, 1, first[0], 10, key, *regs)
        assert len(plain) == 4
        if counted:
            # 10 tokens of a 128 bucket: one chunk of two in 6 linear layers
            so_far = jnp.asarray([5, 7, 11, 13, 17], jnp.int32)
            want = [5 + 8 * 384, 7 + 8 * 512, 11 + 6, 13 + 12, 17 + 8 * 384]
            *_, total = b._insert_fn(
                fresh(), one, 1, first[0], 10, key, *regs, so_far, *counts)
            assert total.tolist() == want
            *_, total = b._insert_many_fn(
                fresh(), one, jnp.asarray([2], jnp.int32), first,
                jnp.asarray([10], jnp.int32), key[None], *regs, so_far, *counts)
            assert total.tolist() == want
    finally:
        if not counted:
            b.close()


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


def _equations(jaxpr):
    """The primitives of a jaxpr and of the jaxprs inside it, counted."""
    import collections

    from jax.extend import core

    found = collections.Counter()
    for eqn in jaxpr.eqns:
        found[eqn.primitive.name] += 1
        for inner in jax.tree_util.tree_leaves(
                eqn.params, is_leaf=lambda p: isinstance(
                    p, (core.Jaxpr, core.ClosedJaxpr))):
            if isinstance(inner, core.ClosedJaxpr):
                inner = inner.jaxpr
            if isinstance(inner, core.Jaxpr):
                found.update(_equations(inner))
    return found


@pytest.mark.parametrize("group_rows", [4096, 16])
def test_without_held_the_grouped_path_traces_to_the_parents(group_rows):
    """``held=None`` (the afmoe block's prefill, every family's decode off a
    TPU) at shapes the grouped kernel does not take: the rows' sums of
    ``grouped_experts`` trace to the parent's lines (since PR 43 with the
    mask of the pairs that are no expert's, which a pad row's are), in one
    piece and in groups."""
    def one(x, picks, weights, w1, w3, w2):
        # every pair is moved, and the dots work every row they are given
        return (_parent_grouped(x, picks, weights, w1, w3, w2, True),
                jnp.stack([jnp.int32(picks.size), jnp.int32(picks.size)]))

    def parent(x, picks, weights, w1, w3, w2):
        n = x.shape[0]
        if n <= group_rows:
            return one(x, picks, weights, w1, w3, w2)
        groups = -(-n // group_rows)
        while n % groups:
            groups += 1
        split = lambda a: a.reshape(groups, n // groups, *a.shape[1:])  # noqa: E731
        out, counts = lax.map(
            lambda r: one(r[0], r[1], r[2], w1, w3, w2),
            (split(x), split(picks), split(weights)))
        return out.reshape(n, x.shape[1]), counts.sum(axis=0)

    x, picks, weights, stacks = _share_case(rows=48)
    args = (x, picks % 4, weights, *stacks[0])

    def mine(*args):
        return experts.grouped_experts.__wrapped__(*args, group_rows=group_rows)

    # the same primitives (the parent gathers the pairs' weights after its
    # dots and masks a column, ``_pairs_ffn`` is handed both): the three
    # dots, no platform switch, no kernel
    traced = _equations(jax.make_jaxpr(mine)(*args).jaxpr)
    assert set(traced) == set(_equations(jax.make_jaxpr(parent)(*args).jaxpr))
    assert traced["ragged_dot_general"] == 3
    assert not {"pallas_call", "platform_index", "cond"} & set(traced)
    got, want = experts.grouped_experts(
        *args, group_rows=group_rows), jax.jit(parent)(*args)
    np.testing.assert_array_equal(got[0], want[0])
    assert got[1].tolist() == want[1].tolist() == [192, 192]


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
