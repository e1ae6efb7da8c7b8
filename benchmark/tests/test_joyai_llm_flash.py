"""The joyai_llm_flash architecture's benchmark files hold what the others'
hold: the manifest finds them, the configuration states every published
width and its cut, the costs are the file's own arithmetic, each new reader
reads a fixture and falls silent without its counter or its kernel, the
served model agrees with the plain reference at a tiny size and each wrong
one does not, and the tiny CPU rehearsal runs the configuration end to end.
CPU only.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_joyai_llm_flash.py -q
"""

from __future__ import annotations

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark import manifest, traffic  # noqa: E402
from benchmark.tests.test_benchmark import (  # noqa: E402
    TINY_MIX, _copy_of_the_benchmark, _rehearse)

CONFIG = "joyai-llm-flash"
CELL = CONFIG + ".reasoning"
NEW_METRICS = ("mla_latent_hbm_roofline", "mla_latent_read_share",
               "mla_step_bytes_share")
JOINED = ("moe_expert_hbm_roofline", "moe_held_rows_share",
          "moe_held_rows_per_touched_expert", "lane_occupancy",
          "device_idle_share.batch", "admit_turn_max_ms", "read_wait_max_ms",
          "dispatch_found_drained_share", "moe_prefill_pairs_moved_share")
REDUCED = ["num_hidden_layers", "n_routed_experts", "vocab_size",
           "max_position_embeddings"]
# the catalog row's ``config`` (model-configs/architectures.jsonl,
# JoyAI-LLM-Flash), as the file must hold it but for REDUCED
CATALOG = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
    "head_dim": 64, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 7168, "kv_lora_rank": 512,
    "max_position_embeddings": 131072, "model_type": "joyai_llm_flash",
    "moe_intermediate_size": 768, "moe_layer_freq": 1, "n_group": 1,
    "n_routed_experts": 256, "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 8,
    "num_hidden_layers": 40, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 1, "q_lora_rank": 1536, "qk_head_dim": 192,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_interleave": True, "rope_scaling": None, "rope_theta": 32000000,
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1, "topk_method": "noaux_tc",
    "v_head_dim": 128, "vocab_size": 129280}


@pytest.fixture(scope="module")
def man():
    return manifest.load(ROOT)


@pytest.fixture(scope="module")
def cfg(man):
    return manifest.config(ROOT, man, CONFIG)


@pytest.fixture(scope="module")
def arch(man, cfg):
    return manifest.architecture(ROOT, man, cfg["architecture"])


def test_the_cell_its_files_and_its_metrics_are_found(man, cfg, arch):
    cell = manifest.cell(man, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "reasoning", 1)
    assert len(cell["why"]) <= 200
    assert arch.__name__ == "benchmark.architectures.joyai_llm_flash"
    assert all(hasattr(arch, name) for name in manifest.ARCHITECTURE_API)
    got = {m["name"] for m in manifest.metrics_of(man, "per_layer", CELL)}
    assert set(NEW_METRICS) | set(JOINED) <= got
    assert {"decode_step_device_ms", "decode_hbm_roofline", "scheduler_host_share",
            "prefill_device_share", "load_s", "warm_s"} <= got
    assert {m["name"] for m in manifest.metrics_of(man, "end_to_end", CELL)} == {
        "tpot_p50_ms", "tokens_per_s", "setup_s"}
    assert "device_idle_share.latency" not in got
    # the accepted reader of that name takes ``num_experts`` from the file,
    # a key this family's published config does not have
    assert "moe_experts_touched_share" not in got
    assert "moe_rows_per_touched_expert" not in got
    for name in NEW_METRICS:
        entry = next(m for m in man["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [CELL] and entry["moves"] == "tpot_p50_ms"
        assert entry["unit"] == "%"
        assert callable(manifest.layer_reader(ROOT, man, name))
    # the cell was appended to every list it joined, and to nothing else
    for name in JOINED + ("tokens_per_s",):
        entry = next(m for m in man["per_layer"] + man["end_to_end"]
                     if m["name"] == name)
        assert CELL in entry["workloads"]
    assert cell in man["workloads"] and any(
        c["name"] == CONFIG for c in man["configs"])
    assert len(json.dumps(man)) < 64 << 10


def test_the_configuration_states_every_width_and_its_cut(man, cfg, arch):
    entry = next(c for c in man["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == cfg["reduced"] == REDUCED
    assert entry["source"] == cfg["source"]
    assert set(cfg["reduced_why"]) == set(REDUCED)
    for key in ("assumed", "deployment", "memory_arithmetic", "weights", "server"):
        assert cfg[key], key
    # every key of the catalog row, unchanged but for the four in reduced
    assert {k: cfg[k] for k in CATALOG if k not in REDUCED} == {
        k: v for k, v in CATALOG.items() if k not in REDUCED}
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"], cfg["vocab_size"],
            cfg["max_position_embeddings"]) == (12, 32, 16160, 6144)
    # the published counts and the share are stated beside the cut ones
    assert cfg["n_routed_experts_published"] == CATALOG["n_routed_experts"]
    assert cfg["num_hidden_layers_published"] == CATALOG["num_hidden_layers"]
    assert cfg["vocab_size_published"] == CATALOG["vocab_size"] == 8 * cfg["vocab_size"]
    assert cfg["experts_held"] == [0, 32] and arch.held(cfg) == (0, 32)
    assert cfg["server"] == {"slots": 64, "max_seq": 6144}
    for said in ("v5e-16", "two pipeline stages", "eight chips"):
        assert said in cfg["deployment"], said
    # the floors: the dense layer once and at least four expert layers, at
    # least 8 experts, an eighth of the vocabulary
    assert arch.n_dense(cfg) == 1 and cfg["num_hidden_layers"] - 1 >= 4
    assert cfg["n_routed_experts"] >= 8
    kw = arch.model_kwargs(cfg, 2**31 + 5)
    assert kw["seed"] < 2**31 and kw["block"] == "joyai_llm_flash"
    assert (kw["n_routed_experts"], kw["experts_held"], kw["experts_per_tok"],
            kw["route_scale"]) == (256, [0, 32], 8, 2.5)
    assert (kw["n_layers"], kw["max_seq"], kw["vocab_size"], kw["n_dense_layers"]) == (
        12, 6144, 16160, 1)
    assert (kw["q_lora_rank"], kw["kv_lora_rank"], kw["qk_nope_head_dim"],
            kw["qk_rope_head_dim"], kw["v_head_dim"], kw["head_dim"]) == (
        1536, 512, 128, 64, 128, 192)
    assert (kw["d_ff"], kw["expert_width"], kw["n_shared_experts"]) == (7168, 768, 1)
    assert set(arch.rehearsal(cfg)) <= set(cfg)
    assert {"latent_attention", "rope_interleave", "cache_row", "router",
            "mtp_not_served", "kv_b_proj_as_two_stacks"} <= set(cfg["assumed"])
    assert cfg["num_nextn_predict_layers"] == 1      # stated as published
    for bad in (dict(cfg, experts_held=[0, 16]), dict(cfg, served_layers=[0, 1])):
        with pytest.raises(manifest.ManifestError):
            arch.model_kwargs(bad, 0)
    for key, value in (("rope_interleave", False), ("scoring_func", "softmax"),
                       ("n_group", 8), ("qk_head_dim", 128)):
        with pytest.raises(manifest.ManifestError):
            arch.model_kwargs(dict(cfg, **{key: value}), 0)


def test_reasoning_gives_the_configurations_lanes_their_clients(man, cfg):
    mix = manifest.traffic(ROOT, man, "reasoning")
    assert traffic.n_clients(mix, cfg["server"]["slots"]) == 64 + 8
    # the traffic ISSUE 42 named, its ramp too: the window is not placed by
    # the waves of the program at its present speed (the mix's own ``why``)
    assert (mix["loop"], mix["ramp_s"], mix["drain_s"], mix["temperature"]) == (
        "closed", 20, 0, 0.0)
    cycle = traffic.cycle(mix)
    assert sum(p for _k, p, _n in cycle) / len(cycle) == 1088
    assert sum(n for _k, _p, n in cycle) / len(cycle) == 2944
    ends = sorted({p + n for _k, p, n in cycle})
    assert ends == [1792, 3328, 5120, 5888] and ends[-1] <= cfg["server"]["max_seq"]
    assert ends[-1] == manifest.architecture(
        ROOT, man, cfg["architecture"]).PROMPT_LEN
    # every prompt in a bucket the batcher has: none pads to max_seq
    assert max(traffic.prompt_lens(mix)) <= 1792


def test_costs_against_the_configs_own_arithmetic(cfg, arch):
    assert arch.expert_params(cfg) * arch.BYTES == 9_437_184          # 9.44 MB
    assert arch.latent_bytes_per_position(cfg) == 1152
    assert arch.mla_params(cfg) == 26_347_520                         # 52.7 MB
    # without the program's counters: nothing, not a guess
    assert arch.decode_step_bytes(cfg, 1000, {}) is None
    assert arch.mla_step_bytes(cfg, {"moe_layer_steps": 11}) is None
    steps = 50
    counters = {"moe_layer_steps": 11 * steps,
                "moe_experts_touched": 11 * steps * 27.8,
                "mla_positions_live": 12 * steps * 64 * 2927}
    none_live = arch.decode_step_bytes(cfg, 0, counters)
    experts = 11 * 27.8 * 9_437_184
    # outside the experts: 12 x 52.7 MB of attention, 11 x 10.5 MB of
    # routers and shared experts, 88 MB of dense FFN, 66 MB of head
    assert none_live - experts == pytest.approx(0.902e9, rel=0.01)
    assert experts == pytest.approx(2.89e9, rel=0.01)
    assert arch.decode_step_bytes(cfg, 1000, counters) - none_live == 1000 * 12 * 1152
    # ISSUE 42's step: 6.4 GB, of which latent attention 51%
    mine, step = arch.mla_step_bytes(cfg, counters)
    assert step == pytest.approx(6.38e9, rel=0.01)
    assert step == arch.decode_step_bytes(cfg, 64 * 2927, counters)
    assert mine / step == pytest.approx(0.505, abs=0.01)
    one = arch.prefill_flops(cfg, 1792, 1, {})
    assert arch.prefill_flops(cfg, 2 * 1792, 2, {}) == pytest.approx(2 * one)
    assert arch.prefill_flops(cfg, 1792 + 512, 2, {}) < one + arch.prefill_flops(
        cfg, 512, 1, {})
    assert arch.prefill_flops(cfg, 0, 0, {}) == 0.0
    assert arch.prefill_attention_flops(cfg, 1792, 1) == pytest.approx(
        2 * 32 * (192 + 128) * 12 * 1792 * 1792 / 2)
    # the experts held here take an eighth of the picks
    assert 0.7e9 < one / 1792 < 1.4e9


def _run(cfg, arch, counters, device_ops=()):
    return {"config": cfg, "architecture": arch,
            "peaks": {"hbm_bytes_per_s": 819e9},
            "trace": {"device_ops": [list(op) for op in device_ops]},
            "trace_counters": ({}, {"program": {"counters": counters}})}


def test_each_new_reader_on_a_fixture_and_without_its_counters(man, cfg, arch):
    read = {name: manifest.layer_reader(ROOT, man, name)
            for name in NEW_METRICS + JOINED[:3]}
    steps = 800
    live = 64 * 2927 * 12 * steps
    counters = {"moe_layer_steps": 11 * steps,
                "moe_experts_touched": int(11 * steps * 27.8),
                "moe_rows_routed": 11 * steps * 512, "moe_rows_held": 11 * steps * 64,
                "mla_positions_live": live, "mla_positions_read": int(live * 1.02),
                "mla_lane_steps": 64 * 12 * steps}
    ops = [("jit_fused_burst:latent_decode_attention_bf16_64_32_512", 3.0),
           ("jit_fused_burst:touched_experts_ffn_f32_64_2048", 3.1),
           ("jit_prefill_many:latent_prefill_attention_bf16_256_1792_128", 9.0)]
    run = _run(cfg, arch, counters, ops)
    assert read["mla_latent_read_share"](run) == pytest.approx(102.0, abs=0.01)
    # 1.02 x 64 x 2927 x 12 positions x 1,152 B a step at 819 GB/s over 3 s
    assert read["mla_latent_hbm_roofline"](run) == pytest.approx(
        100 * int(live * 1.02) * 1152 / 819e9 / 3.0)
    assert 60 < read["mla_latent_hbm_roofline"](run) < 100
    assert read["mla_step_bytes_share"](run) == pytest.approx(50.5, abs=1.0)
    # the readers the cell joined size an expert and count from the file
    assert read["moe_held_rows_share"](run) == pytest.approx(12.5)
    assert read["moe_held_rows_per_touched_expert"](run) == pytest.approx(
        64 / 27.8, rel=0.01)
    assert read["moe_expert_hbm_roofline"](run) == pytest.approx(
        100 * int(11 * steps * 27.8) * 9_437_184 / 819e9 / 3.1)
    # a program without the counters (the parent), or a trace without the
    # kernel: nothing, and no error
    for empty in (_run(cfg, arch, {}), _run(cfg, arch, {"tokens": 5}),
                  {**run, "trace_counters": None}):
        assert all(read[name](empty) is None for name in NEW_METRICS)
    assert read["mla_latent_hbm_roofline"](_run(cfg, arch, counters)) is None


@pytest.fixture(scope="module")
def tiny(cfg, arch):
    small = dict(cfg, **arch.rehearsal(cfg), name="tiny")
    kw = arch.model_kwargs(small, 7)
    seed = kw.pop("seed")
    model = arch.SeededJoyaiLLMFlashLM(**kw)
    return model, model.init_params(seed)


@pytest.fixture
def tiny_batcher(tiny):
    """The comparison builds no batcher of its own: a test passes one, of
    the tiny cell's size (32 lanes as the comparison's batch, a cache of
    640 + 3 positions rounded up to the ragged read's block)."""
    from seldon_core_tpu.serving.continuous import ContinuousBatcher

    model, params = tiny
    batcher = ContinuousBatcher(model, params, slots=32, max_seq=768)
    yield batcher
    batcher.close()


def test_the_served_model_agrees_with_the_reference_at_a_tiny_size(
        arch, tiny, tiny_batcher):
    import jax

    model, params = tiny
    assert type(model).__mro__[1].__name__ == "JoyaiLLMFlashLM"
    assert all(a.dtype == jax.numpy.bfloat16
               for a in jax.tree_util.tree_leaves(params))
    out = arch.compare_served(model, params, seed=2**31 + 3, prompt_len=640,
                              decode_steps=3, batcher=tiny_batcher)
    assert out["ok"] and out["ratio"] < arch.TOLERANCE, out
    assert out["picks_margin"] <= arch.PICKS_MARGIN and out["picks_agree"] > 0.9
    assert out["rows_ratio"] <= arch.ROWS_TOLERANCE
    assert out["ratio_short_prefill"] < arch.TOLERANCE
    # the burst's batch, most lanes live: 28 lanes x 3 steps and the prefill's last
    assert (out["lanes"], out["lanes_live"], out["positions"]) == (32, 28, 85)
    assert (out["cache_len"], out["borrowed"]) == (768, False)
    assert out["counters_are_the_picks"] and out["burst_counters_hold"]
    # 28 lanes x 4 picks of 16 experts, 4 held: all touched, a quarter lands
    assert out["experts_touched_a_layer_step"] == 4
    assert 0.15 < out["held_rows_share"] < 0.35


def test_the_comparisons_lanes_are_the_cells(cfg, arch):
    """The comparison's batch is the configuration's: most lanes live, every
    eighth idle, lengths from 256 to where the cell's contexts end, one lane
    going on where the whole prompt ended, three at a block's edge, and no
    two stepping at one position."""
    assert cfg["server"]["slots"] == 64
    from seldon_core_tpu.ops.latent_attention import LATENT_BLOCK

    # asked of the program, not held here: 512 today
    assert arch.read_block() == LATENT_BLOCK == 512
    for block in (512, 256):
        start = arch.lane_lengths(64, arch.PROMPT_LEN, 4, block)
        lens = sorted(start.values())
        assert len(start) == 56 and set(range(64)) - set(start) == set(
            range(5, 64, 8))
        assert lens[0] == 256 and lens[-1] == 5888
        assert {n % block for n in lens} >= {0, 1, block - 1}
        assert min(b - a for a, b in zip(lens, lens[1:])) >= 4
    with pytest.raises(ValueError):
        arch.lane_lengths(64, 100, 4, 512)


def test_the_read_block_is_asked_of_the_program_when_the_comparison_runs(
        arch, tiny, monkeypatch):
    """A program whose latent read streams blocks of 256 (``LATENT_BLOCK``
    patched; on a CPU the dots run and the step counts what the kernel
    would stream) is held to ``256 x ceil(len / 256)``, lengths on both
    sides of ITS block, and passes; the equality stays in: a comparison
    that asked 512 of the same program does not hold."""
    from seldon_core_tpu.ops import latent_attention
    from seldon_core_tpu.serving.continuous import ContinuousBatcher

    model, params = tiny
    monkeypatch.setattr(latent_attention, "LATENT_BLOCK", 256)
    assert arch.read_block() == 256
    served = {}
    for asked in (256, 512):
        if asked != 256:
            monkeypatch.setattr(arch, "read_block", lambda: asked)
        batcher = ContinuousBatcher(model, params, slots=32, max_seq=768)
        try:
            served[asked] = arch.serve(model, params, 2**31 + 3,
                                       prompt_len=640, decode_steps=3,
                                       batcher=batcher)
        finally:
            batcher.close()
    mine = served[256]
    assert mine["read_block"] == 256 and mine["counters_hold"]
    assert mine["burst_counters_hold"]
    out = arch.judge(model, mine, params)
    assert out["ok"] and out["read_block"] == 256, out
    assert not served[512]["counters_hold"]
    assert not arch.judge(model, served[512], params)["ok"]


@pytest.mark.parametrize("variant", [
    "weights_8bit", "latent_8bit", "rotary_half_split", "route_scale_1",
    "scale_128", "latent_unnormed"])
def test_a_wrong_reference_is_not_agreed_with(arch, tiny, tiny_batcher,
                                              variant):
    model, params = tiny
    out = arch.compare_served(model, params, seed=2**31 + 3, prompt_len=640,
                              decode_steps=3, variant=variant,
                              batcher=tiny_batcher)
    assert not out["ok"], out
    assert (out["ratio"] > arch.TOLERANCE or out["picks_margin"] > arch.PICKS_MARGIN
            or out["rows_ratio"] > arch.ROWS_TOLERANCE), out
    with pytest.raises(ValueError):
        arch.compare_served(model, params, seed=1, prompt_len=640,
                            decode_steps=3, variant="no_such_model",
                            batcher=tiny_batcher)


def test_a_burst_that_leaves_a_live_lane_out_is_not_agreed_with(
        arch, tiny, tiny_batcher):
    """The burst's own control: its tokens, its rows and its counters are
    held to the step's, and the reference alone would not see it."""
    model, params = tiny
    out = arch.compare_served(model, params, seed=2**31 + 3, prompt_len=640,
                              decode_steps=3, variant="burst_idles_a_lane",
                              batcher=tiny_batcher)
    assert not out["ok"], out
    assert out["ratio"] <= arch.TOLERANCE and out["rows_ratio"] <= arch.ROWS_TOLERANCE
    assert out["burst_rows_ratio"] > arch.TOLERANCE
    assert not out["burst_counters_hold"]


def test_the_comparison_borrows_the_serving_batchers_cache_and_hands_it_back(
        arch, tiny):
    """On the chip a second cache of the cell's size does not fit: the
    comparison runs on the cache and the executables of the batcher that
    serves the parameters, and leaves it serving; where the process has
    none and none is given, it says so and builds none."""
    import gc

    import numpy as np

    from seldon_core_tpu.serving.continuous import ContinuousBatcher

    model, params = tiny
    gc.collect()    # the batchers of the tests before this one
    with pytest.raises(ValueError, match="none was given"):
        arch.compare_served(model, params, seed=11, decode_steps=3)
    batcher = ContinuousBatcher(model, params, slots=8, max_seq=512)
    try:
        assert arch._serving_batcher(params) is batcher
        out = arch.compare_served(model, params, seed=11, decode_steps=3)
        assert out["ok"] and out["borrowed"], out
        assert (out["lanes"], out["cache_len"], out["prompt_len"]) == (8, 512, 384)
        assert batcher._cache is not None
        batcher.start()
        prompt = np.random.default_rng(0).integers(0, 1024, size=40).tolist()
        first = list(batcher.submit(prompt, max_new_tokens=5).result(timeout=300))
        again = list(batcher.submit(prompt, max_new_tokens=5).result(timeout=300))
        assert first == again and len(first) == 45
    finally:
        batcher.close()


def test_the_configuration_is_rehearsed_end_to_end(tmp_path):
    """The cell's configuration under a tiny mix in a copy: served through
    the engine by the module's family, compared by its ``compare_served``
    on the engine's own cache, and the program's counters reach the new
    metrics."""
    bench, man = _copy_of_the_benchmark(tmp_path)
    (bench / "traffic" / "tiny.json").write_text(json.dumps(TINY_MIX))
    man["workloads"].append({"name": CONFIG + ".tiny", "config": CONFIG,
                             "traffic": "tiny", "chips": 1, "why": "test"})
    for m in man["per_layer"]:
        if m["name"] in NEW_METRICS + JOINED[:3] + JOINED[-1:]:
            m["workloads"].append(CONFIG + ".tiny")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(man))
    out, line = _rehearse(tmp_path, CONFIG + ".tiny", "2")
    assert line["correct"] and line["failed"] == 0 and line["attempted"] > 0
    assert "'rows_ratio'" in out and "'picks_margin'" in out
    assert "'borrowed': True" in out
    got = line["metrics"]
    assert {"tpot_p50_ms", "setup_s", "decode_step_device_ms",
            "decode_hbm_roofline", "scheduler_host_share", "prefill_device_share",
            "load_s", "warm_s"} <= set(got)
    # 4 of 16 experts held, 4 picks a live lane; lengths round up to 512
    assert 5.0 < got["moe_held_rows_share"]["value"] < 60.0
    assert got["moe_held_rows_per_touched_expert"]["value"] >= 1.0
    assert got["mla_latent_read_share"]["value"] >= 100.0
    assert 0.0 < got["mla_step_bytes_share"]["value"] < 100.0
    # the prefills' grouped experts move a room of the pairs, not all
    assert 0.0 < got["moe_prefill_pairs_moved_share"]["value"] <= 100.0
    # the kernels run on a TPU only: their readers find nothing here
    assert "mla_latent_hbm_roofline" not in got
    assert "moe_expert_hbm_roofline" not in got
    run_dir, = (bench / "_runs" / (CONFIG + ".tiny")).glob("*-trace2-0")
    served = json.load(open(run_dir / "model" / "jax_config.json"))
    assert served["family"] == "benchmark_joyai_llm_flash"
    assert served["config"]["block"] == "joyai_llm_flash"
    counters = json.load(open(run_dir / "capture.json"))["counters"]
    assert counters["mla_lane_steps"] > 0 and counters["moe_rows_held"] > 0
    assert counters["mla_positions_live"] <= counters["mla_positions_read"]
    assert counters["moe_prefill_pairs_routed"] > 0
