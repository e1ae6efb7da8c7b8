"""The mimo_v2 family through the serving path: ``ContinuousBatcher`` drives
it through the same submit, admit, burst, read and credit loop as every
other family. Greedy tokens are the plain reference's generation loop's
(``benchmark/reference/mimo_v2.py``) for prompts under the window, on it,
past it and past its multiples, in different buckets, admitted together and
beside live lanes; lanes are taken again while others decode (a ring's next
occupant overwrites it whole or reads only what it wrote); the family's
counters come home with the bursts. A small size, float32, on the CPU."""

import numpy as np
import pytest

from benchmark.reference import mimo_v2 as reference
from seldon_core_tpu.models.llm import DecoderLM
from seldon_core_tpu.serving.continuous import ContinuousBatcher

KINDS = (["full_attention"] + ["sliding_attention"] * 4
         + ["full_attention", "sliding_attention"])
SMALL = dict(
    block="mimo_v2", vocab_size=97, d_model=64, n_layers=7, n_heads=8,
    n_kv_heads=1, head_dim=24, d_ff=128, max_seq=256, rope_theta=1e7,
    swa_rope_theta=1e4, norm_eps=1e-5, dtype="float32", layer_types=KINDS,
    v_head_width=16, rotary_dim=8, swa_window=16, swa_n_kv_heads=2,
    n_dense_layers=1, n_routed_experts=32, experts_per_tok=4, expert_width=32,
    experts_held=(4, 4), residual_scale=0.5)


@pytest.fixture(scope="module")
def served():
    model = DecoderLM(**SMALL)
    params = model.init_params(3)
    keep = ContinuousBatcher.MIN_ATTN_BUCKET
    ContinuousBatcher.MIN_ATTN_BUCKET = 16
    batcher = ContinuousBatcher(
        model, params, slots=4, max_seq=256, prefill_buckets=(16, 32, 64),
        steps_per_poll=4, attn_bucket=16)
    yield model, params, batcher
    batcher.close()
    ContinuousBatcher.MIN_ATTN_BUCKET = keep


def _prompt(seed, n):
    return [int(t) for t in np.random.default_rng(seed).integers(0, 97, size=n)]


def test_the_live_caches_shapes_are_the_kinds(served):
    """The batcher's own cache: the window layers' arrays are a window
    long with their KV heads, the full
    layers' ``max_seq`` long with theirs, keys
    in rows of whole registers beside narrower values; and the scheduler's
    prices are the family's."""
    model, _params, batcher = served
    cache = batcher._cache
    assert [a.shape for a in cache["k"]] == [(4, 1, 256, 128)] * 2
    assert [a.shape for a in cache["v"]] == [(4, 1, 256, 16)] * 2
    assert [a.shape for a in cache["wk"]] == [(4, 2, 16, 128)] * 5
    assert [a.shape for a in cache["wv"]] == [(4, 2, 16, 16)] * 5
    assert batcher._position_layers == 14
    assert batcher._kv_key_bytes == 2 * (128 + 16) * 4
    assert batcher._lane_bytes(100) == 100 * batcher._kv_key_bytes + (
        16 * 5 * 2 * (128 + 16) * 4)
    # the family counts its rings' reads itself: the scheduler's window
    # arithmetic (a max_seq-long cache read from a block on) adds nothing
    assert batcher._kv_windows == () and model.attention_kinds()[1] == (5, 16)


def test_two_prompts_of_different_buckets_are_admitted_in_one_turn(
        served, monkeypatch):
    model, params, batcher = served
    asked = [(_prompt(1, 9), 9), (_prompt(2, 40), 5)]
    with monkeypatch.context() as held:
        held.setattr(batcher, "start", lambda: None)
        futures = [batcher.submit(p, max_new_tokens=new) for p, new in asked]
    batcher.start()
    for (prompt, new), f in zip(asked, futures):
        got = f.result(timeout=600)
        assert got[:len(prompt)] == prompt
        assert got[len(prompt):] == reference.generate(
            params, model.cfg, prompt, new)
    polls = [e for e in batcher.flight.snapshot() if e.get("type") == "poll"]
    assert [e["admitted"] for e in polls if e.get("admitted")] == [2]


@pytest.mark.parametrize("n,new", [(1, 20), (15, 4), (16, 3), (17, 18),
                                   (31, 5), (64, 3), (100, 14)])
def test_greedy_tokens_are_the_reference_loops(served, n, new):
    """Under the window and decoding through it (1 + 20), on its edges (15,
    16, 17; 17 + 18 crosses 32), on a bucket's edge (64), past the last
    bucket and across a multiple of the window (100 + 14 crosses 112)."""
    model, params, batcher = served
    prompt = _prompt(10 + n, n)
    got = batcher.submit(prompt, max_new_tokens=new).result(timeout=600)
    assert got[:n] == prompt
    assert got[n:] == reference.generate(params, model.cfg, prompt, new)


def test_lanes_admitted_beside_live_ones_and_the_counters_come_home(served):
    """Six requests over four lanes: lanes freed and taken again while
    others decode; a ring's last occupant's rows lie past what its next
    one reads until it overwrites them."""
    model, params, batcher = served
    before = dict(batcher.stats)
    asked = [(_prompt(100 + n, n), new)
             for n, new in ((24, 11), (2, 19), (14, 6), (33, 8), (5, 12), (61, 4))]
    futures = [batcher.submit(p, max_new_tokens=new) for p, new in asked]
    for (prompt, new), f in zip(asked, futures):
        assert f.result(timeout=600)[len(prompt):] == reference.generate(
            params, model.cfg, prompt, new)
    stats = {k: v - before.get(k, 0) for k, v in batcher.stats.items()
             if isinstance(v, (int, float))}
    steps = stats["moe_layer_steps"] // 6
    assert steps > 0 and stats["moe_layer_steps"] % 6 == 0
    # 4 picks a live lane in 6 expert layers, an eighth of them held
    assert stats["moe_rows_routed"] % (4 * 6) == 0
    assert 0.04 < stats["moe_rows_held"] / stats["moe_rows_routed"] < 0.3
    assert 0 < stats["moe_experts_touched"] <= stats["moe_rows_held"]
    lane_steps = stats["moe_rows_routed"] // (4 * 6)
    # the full layers: what the lanes hold, and what the read streams
    assert 0 < stats["kv_rows_live"] <= stats["kv_rows_read"]
    # the rings: a query sees at most the window of what a max_seq-long
    # cache would hold; on the CPU the dots read every lane's ring whole
    assert stats["kv_positions_live_window"] * 2 == stats["kv_rows_live"] * 5
    assert 0 < stats["kv_positions_seen_window"] <= min(
        16 * 5 * lane_steps, stats["kv_positions_live_window"])
    assert stats["kv_positions_read_window"] == 4 * 16 * 5 * steps
    # 14 rows a live lane and step, none of them by the kernel here
    assert stats["kv_rows_written"] == 14 * lane_steps
    routed = stats["moe_prefill_pairs_routed"]
    assert routed == stats["prefill_tokens"] * 4 * 6 > 0
    assert 0 < stats["moe_prefill_pairs_moved"] <= routed
