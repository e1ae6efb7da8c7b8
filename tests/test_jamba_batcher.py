"""The jamba family through the serving path: ``ContinuousBatcher`` drives
it through the same submit, admit, burst, read and credit loop as every
other family. Greedy tokens are the plain reference's generation loop's
(``benchmark/reference/jamba.py``) for prompts shorter than the convolution,
in different buckets, admitted together and beside live lanes, and into a
lane another request has used: its state and tails must not leak into the
next occupant's. The family's counters come home with the bursts. A small
size, float32, on the CPU."""

import numpy as np
import pytest

from benchmark.reference import jamba as reference
from seldon_core_tpu.models.llm import DecoderLM
from seldon_core_tpu.serving.continuous import ContinuousBatcher

SMALL = dict(
    block="jamba", vocab_size=97, d_model=64, n_layers=6, n_heads=4,
    n_kv_heads=1, head_dim=128, d_ff=128, max_seq=256, norm_eps=1e-6,
    dtype="float32", attn_layer_period=4, attn_layer_offset=1,
    mamba_d_state=16, mamba_d_conv=4, mamba_dt_rank=8, mamba_expand=2,
    residual_scale=0.5)
N_MAMBA, N_FULL = 4, 2


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
    """Both wait when the scheduler starts (``submit`` starts it: held back
    here until both are queued): one turn admits them (two prefills, one a
    bucket), and each is the reference loop's."""
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


@pytest.mark.parametrize("n,new", [(1, 6), (2, 5), (3, 5), (4, 4), (17, 9),
                                   (64, 3), (100, 5)])
def test_greedy_tokens_are_the_reference_loops(served, n, new):
    """Prompts shorter than the convolution (1, 2, 3), as long (4), on a
    bucket's edge (64) and past the last bucket (100)."""
    model, params, batcher = served
    prompt = _prompt(10 + n, n)
    got = batcher.submit(prompt, max_new_tokens=new).result(timeout=600)
    assert got[:n] == prompt
    assert got[n:] == reference.generate(params, model.cfg, prompt, new)


def test_a_used_lane_is_readmitted_and_the_counters_come_home(served):
    """Nine requests over four lanes: every lane is freed and taken again
    while others decode. A lane's state and tails are its last occupant's
    until the next insert replaces them WHOLE: a state that leaked would
    move the next occupant's every token."""
    model, params, batcher = served
    before = dict(batcher.stats)
    asked = [(_prompt(100 + n, n), new) for n, new in (
        (24, 11), (2, 9), (14, 6), (33, 8), (5, 12), (61, 4), (3, 7),
        (47, 10), (9, 5))]
    futures = [batcher.submit(p, max_new_tokens=new) for p, new in asked]
    for (prompt, new), f in zip(asked, futures):
        assert f.result(timeout=600)[len(prompt):] == reference.generate(
            params, model.cfg, prompt, new)
    stats = {k: v - before.get(k, 0) for k, v in batcher.stats.items()
             if isinstance(v, (int, float))}
    assert stats["admitted"] == 9 > batcher.slots
    steps = stats["ssm_layer_steps"] // N_MAMBA
    assert steps > 0 and stats["ssm_layer_steps"] % N_MAMBA == 0
    # a state update a live lane and Mamba layer; at most every lane live
    assert stats["ssm_lane_steps"] % N_MAMBA == 0
    assert 0 < stats["ssm_lane_steps"] <= steps * batcher.slots * N_MAMBA
    # rows of 2 attention layers: what the lanes hold, and what was read
    assert 0 < stats["kv_rows_live"] <= stats["kv_rows_read"]
    assert stats["kv_rows_live"] % N_FULL == 0
    # the prefills: each prompt's own length against its bucket's
    assert stats["ssm_prefill_steps_walked"] == N_MAMBA * sum(
        len(p) for p, _ in asked)
    assert stats["ssm_prefill_steps_bucket"] == N_MAMBA * stats["prefill_tokens"]
    assert stats["ssm_prefill_steps_walked"] < stats["ssm_prefill_steps_bucket"]


def test_the_burst_through_the_steps_kernels_is_the_reference_loops(monkeypatch):
    """The mixer's two kernels (``ops/selective_scan.py``: the tails in
    place, the state fed ``c`` and ``delta`` as they lie), interpreted, in
    the batcher's own burst at 16 lanes: five requests, so a lane idle
    beside live ones and a whole group of 8 idle lanes at every step; the
    tokens are the reference loop's, and a lane no request took still
    holds the zeros it was made with."""
    from seldon_core_tpu.ops import selective_scan as ss

    traced = []

    def scan(s, layer, x, delta, b, c, a, d, live, mesh=None, walk=None):
        traced.append("state")
        return ss.selective_scan_step_kernel(
            s, layer, x, delta, b, c, a, d, live, walk, interpret=True)

    def tails(t, layer, a, w, bias, live, mesh=None, walk=None):
        traced.append("tails")
        return ss.conv_tail_step_kernel(
            t, layer, a, w, bias, live, walk, interpret=True)

    monkeypatch.setattr(ss, "selective_scan_step", scan)
    monkeypatch.setattr(ss, "conv_tail_step", tails)
    model = DecoderLM(**dict(SMALL, d_model=128, n_layers=4))
    params = model.init_params(5)
    assert ss.steps_in_kernel("tpu", (16, 3, 16, 256))
    batcher = ContinuousBatcher(
        model, params, slots=16, max_seq=128, prefill_buckets=(16, 32),
        steps_per_poll=4, attn_bucket=128)
    try:
        asked = [(_prompt(200 + n, n), new) for n, new in (
            (3, 9), (20, 6), (11, 10), (1, 7), (30, 5))]
        futures = [batcher.submit(p, max_new_tokens=new) for p, new in asked]
        for (prompt, new), f in zip(asked, futures):
            assert f.result(timeout=600)[len(prompt):] == reference.generate(
                params, model.cfg, prompt, new)
        assert batcher.stats["ssm_lane_steps"] > 0
        assert {"state", "tails"} == set(traced)
        for name in ("conv", "state"):
            assert not np.asarray(batcher._cache[name][0])[8:].any()
    finally:
        batcher.close()
