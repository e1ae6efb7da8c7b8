"""The lfm2_moe family through the serving path: ``ContinuousBatcher`` drives
it through the same submit, admit, burst, read and credit loop as every
other family. Greedy tokens are the plain reference's generation loop's
(``benchmark/reference/lfm2_moe.py``) for prompts shorter than the
convolution, in different buckets, admitted together and beside live
lanes; the family's counters come home with the bursts. A small size,
float32, on the CPU."""

import numpy as np
import pytest

from benchmark.reference import lfm2_moe as reference
from seldon_core_tpu.models.llm import DecoderLM
from seldon_core_tpu.serving.continuous import ContinuousBatcher

KINDS = ["conv", "full_attention", "conv", "conv", "conv", "full_attention"]
SMALL = dict(
    block="lfm2_moe", vocab_size=97, d_model=128, n_layers=6, n_heads=4,
    n_kv_heads=2, head_dim=64, d_ff=256, max_seq=256, rope_theta=1e6,
    norm_eps=1e-5, dtype="float32", layer_types=KINDS, n_dense_layers=1,
    n_routed_experts=16, experts_per_tok=4, expert_width=64,
    experts_held=(4, 4), conv_kernel=3, residual_scale=0.5)


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


def test_two_prompts_of_different_buckets_are_admitted_in_one_turn(
        served, monkeypatch):
    """Both wait when the scheduler starts (``submit`` starts it: held
    back here until both are queued): one turn admits them (two prefills,
    one a bucket), and each is the reference loop's."""
    model, params, batcher = served
    asked = [(_prompt(1, 9), 7), (_prompt(2, 40), 5)]
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


@pytest.mark.parametrize("n,new", [(1, 6), (2, 5), (3, 4), (17, 9), (64, 3),
                                   (100, 5)])
def test_greedy_tokens_are_the_reference_loops(served, n, new):
    """Prompts shorter than the convolution (1, 2), as long (3), on a
    bucket's edge (64) and past the last bucket (100: padded to the
    cache's length)."""
    model, params, batcher = served
    prompt = _prompt(10 + n, n)
    got = batcher.submit(prompt, max_new_tokens=new).result(timeout=600)
    assert got[:n] == prompt
    assert got[n:] == reference.generate(params, model.cfg, prompt, new)


def test_lanes_admitted_beside_live_ones_and_the_counters_come_home(served):
    """Six requests over four lanes: lanes freed and taken again while
    others decode; an idle lane's tail is its last occupant's until the
    next insert replaces it whole."""
    model, params, batcher = served
    before = dict(batcher.stats)
    asked = [(_prompt(100 + n, n), new)
             for n, new in ((24, 11), (2, 9), (14, 6), (33, 8), (5, 12), (61, 4))]
    futures = [batcher.submit(p, max_new_tokens=new) for p, new in asked]
    for (prompt, new), f in zip(asked, futures):
        assert f.result(timeout=600)[len(prompt):] == reference.generate(
            params, model.cfg, prompt, new)
    stats = {k: v - before.get(k, 0) for k, v in batcher.stats.items()
             if isinstance(v, (int, float))}
    steps = stats["moe_layer_steps"] // 5
    assert steps > 0 and stats["moe_layer_steps"] % 5 == 0
    # 4 picks a live lane in 5 expert layers, a quarter of them held
    assert stats["moe_rows_routed"] % (4 * 5) == 0
    assert 0.1 < stats["moe_rows_held"] / stats["moe_rows_routed"] < 0.45
    assert 0 < stats["moe_experts_touched"] <= stats["moe_rows_held"]
    # a tail a live lane and convolution layer (4), rows of 2 attention
    # layers: what the lanes hold, and that rounded up as the kernel walks
    lane_steps = stats["moe_rows_routed"] // (4 * 5)
    assert stats["conv_tails_written"] == 4 * lane_steps
    assert 0 < stats["kv_rows_live"] <= stats["kv_rows_read"]
    assert stats["kv_rows_read"] % (2 * 128) == 0
    routed = stats["moe_prefill_pairs_routed"]
    assert routed == stats["prefill_tokens"] * 4 * 5 > 0
    assert 0 < stats["moe_prefill_pairs_moved"] <= routed
