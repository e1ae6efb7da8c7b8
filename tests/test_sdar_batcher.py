"""Generation by blocks through the serving path: ``ContinuousBatcher`` and
``GenerateServer`` drive the sdar_moe family through the same submit, admit,
burst, read and credit loop as every other. Greedy tokens are the plain
reference's generation loop's (``benchmark/reference/sdar_moe.py``), for
every prompt remainder, with ``max_new_tokens`` no multiple of the block,
lanes in different phases of one burst, an eos, a seeded draw; a block is
one span of tokens to the client and one ``gen.block`` span in its trace.
A small size, float32, on the CPU."""

import asyncio
import json

import numpy as np
import pytest

from benchmark.reference import sdar_moe as reference
from seldon_core_tpu import tracing
from seldon_core_tpu.http_server import Request
from seldon_core_tpu.models.llm import DecoderLM
from seldon_core_tpu.ops.decode_attention import walk_block
from seldon_core_tpu.serving.continuous import ContinuousBatcher

MASK = 96
SMALL = dict(vocab_size=97, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
             head_dim=16, max_seq=256, n_routed_experts=8, experts_per_tok=2,
             expert_width=32, dtype="float32", denoising_steps=2,
             mask_token_id=MASK, rope_theta=1e6, norm_eps=1e-6)
W = 4


@pytest.fixture(scope="module")
def served():
    model = DecoderLM(block="sdar_moe", **SMALL)
    params = model.init_params(3)
    keep = ContinuousBatcher.MIN_ATTN_BUCKET
    ContinuousBatcher.MIN_ATTN_BUCKET = 16
    batcher = ContinuousBatcher(
        model, params, slots=4, max_seq=256, prefill_buckets=(16, 32, 64),
        steps_per_poll=4, attn_bucket=16)
    batcher.warm(prompt_lens=(8, 23), max_new_tokens=12, batch_sizes=(1, 4))
    batcher.start()
    yield model, params, batcher
    batcher.close()
    ContinuousBatcher.MIN_ATTN_BUCKET = keep


def _prompt(seed, n):
    return [int(t) for t in np.random.default_rng(seed).integers(0, MASK, size=n)]


@pytest.mark.parametrize("n,new", [(23, 9), (8, 6), (1, 7), (14, 10), (3, 1),
                                   (40, 4)])
def test_greedy_tokens_are_the_reference_loops(served, n, new):
    """Prompt lengths 3, 0, 1, 2, 3 and 0 mod 4; budgets that end inside a
    block (computed whole, emitted up to the budget) and on its edge."""
    model, params, batcher = served
    prompt = _prompt(n, n)
    spans = []
    got = batcher.submit(prompt, max_new_tokens=new,
                         on_tokens=spans.append).result(timeout=300)
    assert got[:n] == prompt
    assert got[n:] == reference.generate(params, model.cfg, prompt, new)
    # a span of tokens to the client is a block: the first what its block
    # had room for beside the prompt's tail, the last cut at the budget
    assert [t for span in spans for t in span] == got[n:]
    sizes = [len(span) for span in spans]
    assert sizes[0] == min(new, W - n % W)
    assert all(size == W for size in sizes[1:-1]) and sizes[-1] <= W


def test_lanes_in_different_phases_share_a_burst(served):
    """Three requests at once, their first blocks holding 0, 3 and 2 prompt
    tokens: in one pass one lane denoises while another commits. Each is
    the reference's own; the family's counters are the blocks' arithmetic."""
    model, params, batcher = served
    before = dict(batcher.stats)
    asked = [(_prompt(100 + n, n), new) for n, new in ((24, 11), (7, 9), (14, 6))]
    futures = [batcher.submit(p, max_new_tokens=new) for p, new in asked]
    for (prompt, new), f in zip(asked, futures):
        assert f.result(timeout=300)[len(prompt):] == reference.generate(
            params, model.cfg, prompt, new)
    diff = {k: batcher.stats[k] - before[k] for k in model.step_counter_names}
    # blocks: 3 + ceil((1 + 9) / 4) = 3 + ceil((2 + 6) / 4) = 3 + 2; a lane
    # overshoots by at most the bursts in flight, whose passes run nothing
    blocks = 3 + 3 + 2
    assert diff["block_commit_forwards"] == blocks
    # every position of a block that was not a prompt's tail was filled in
    assert diff["block_tokens_unmasked"] == blocks * W - (0 + 3 + 2)
    # two denoising passes a whole block, one a block with two or three
    # positions filled, and the commit
    assert diff["block_forwards"] == (3 * 3) + (2 + 2 * 3) + (2 + 3)
    assert diff["moe_rows_routed"] == diff["block_forwards"] * W * 2 * 2
    assert batcher.stats["tokens"] - before["tokens"] == 11 + 9 + 6
    # a pass reads its lane's rows up to the block's end: (passes, length)
    # of each block above, over the 2 layers; what the kernel streams of
    # them rounds each length up to the block of its walk, the rule's own
    # for this model's 2 KV heads of 16 in float32 and this cache of 256
    # (the rows are far under ``COVERS``: the largest block that divides it)
    passes = [(3, 28), (3, 32), (3, 36), (2, 8), (3, 12), (3, 16), (2, 16),
              (3, 20)]
    block = walk_block(model.cfg.n_kv_heads, model.cfg.head_dim, "float32", 256)
    assert block == 256 == batcher._kv_read_block
    assert diff["block_rows_live"] == 2 * sum(n * at for n, at in passes)
    assert diff["block_rows_read"] == 2 * sum(
        n * -(-at // block) * block for n, at in passes)
    # the scheduler's own count of positions read is the one-position
    # bursts': a block pass adds nothing to it
    assert batcher.stats["kv_positions_read"] == before["kv_positions_read"]


def test_an_eos_ends_the_request_inside_its_block(served):
    model, params, batcher = served
    prompt = _prompt(5, 10)
    free = reference.generate(params, model.cfg, prompt, 12)
    eos = free[5]
    got = batcher.submit(prompt, max_new_tokens=12, eos_id=eos).result(
        timeout=300)[len(prompt):]
    assert got == free[:free.index(eos) + 1]
    # the lane is free again, and its next occupant is served whole
    again = batcher.submit(prompt, max_new_tokens=12).result(timeout=300)
    assert again[len(prompt):] == free


def test_a_draw_is_its_seeds_and_greedy_is_the_prompts(served):
    _model, _params, batcher = served
    prompt = _prompt(9, 13)
    run = lambda **kw: batcher.submit(  # noqa: E731
        prompt, max_new_tokens=10, **kw).result(timeout=300)[13:]
    a, b = run(temperature=1.0, seed=4), run(temperature=1.0, seed=4)
    c = run(temperature=1.0, seed=5)
    assert a == b and a != c and MASK not in a + c
    assert run() == run()


def test_the_budget_is_held_to_the_cache(served):
    _model, _params, batcher = served
    from seldon_core_tpu.serving.continuous import PromptTooLong

    with pytest.raises(PromptTooLong):
        batcher.submit(_prompt(1, 250), max_new_tokens=10).result(timeout=60)
    # up to the cache's last block
    got = batcher.submit(_prompt(2, 50), max_new_tokens=206).result(timeout=600)
    assert len(got) == 256


def test_a_block_is_one_span_to_the_client_and_one_in_the_trace(tmp_path):
    from seldon_core_tpu.graph.service import EngineApp
    from seldon_core_tpu.graph.spec import PredictorSpec, default_predictor
    from seldon_core_tpu.servers.generateserver import GenerateServer

    (tmp_path / "jax_config.json").write_text(json.dumps(
        {"family": "llm", "config": dict(SMALL, block="sdar_moe", seed=3)}))
    server = GenerateServer(model_uri=str(tmp_path), slots=2, steps_per_poll=4,
                            attn_bucket=16)
    spec = default_predictor(PredictorSpec.from_dict(
        {"name": "p", "graph": {"name": "gen", "type": "MODEL"}}))
    app = EngineApp(spec, registry={"gen": server})
    tracing.init_tracer("sdar-test", enabled=True)
    try:
        prompt = _prompt(7, 6)
        body = json.dumps({"jsonData": {"prompt_tokens": [prompt],
                                        "max_new_tokens": 9}}).encode()
        resp = asyncio.run(app.rest_app()._dispatch(Request(
            "POST", "/api/v0.1/generate", "",
            {"content-type": "application/json"}, body)))
        assert resp.status == 200
        events = [json.loads(chunk[len(b"data: "):]) for chunk in resp.iterator]
        assert events[-1]["done"] and len(events[-1]["tokens"]) == 15
        model, params = server._model, server.batcher.params
        assert events[-1]["tokens"][6:] == reference.generate(
            params, model.cfg, prompt, 9)
        spans = [s for s in tracing.get_tracer().finished_spans()
                 if s.operation == "gen.block"]
        # blocks of 2 (beside the prompt's tail of 2), 4 and 3 (cut)
        assert [s.tags["tokens"] for s in spans] == [2, 4, 3]
        assert [s.tags["emitted"] for s in spans] == [2, 6, 9]
        ops = {s.operation for s in tracing.get_tracer().finished_spans()}
        assert {"gen.first_token_hold", "gen.decode", "gen.prefill"} <= ops
        ends = [s.start_us + s.duration_us for s in spans]
        assert ends == sorted(ends)
        assert all(b.start_us >= a.start_us for a, b in zip(spans, spans[1:]))
    finally:
        tracing.init_tracer(enabled=False)
        if server.batcher:
            server.batcher.close()
