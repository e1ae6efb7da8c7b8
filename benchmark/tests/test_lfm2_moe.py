"""The lfm2_moe architecture's benchmark files hold what the others' hold:
the manifest finds them, the configuration states every published width
and its cut, the costs are the file's own arithmetic, each new reader reads
a fixture and falls silent without its counter or its kernel, the served
model agrees with the plain reference at a tiny size and each wrong one
does not, and the tiny CPU rehearsal runs the configuration end to end.
CPU only.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_lfm2_moe.py -q
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

CONFIG = "lfm2-24b-a2b"
CELL = CONFIG + ".longdoc"
NEW_METRICS = ("decode_attn_hbm_roofline", "conv_in_proj_device_share",
               "kv_step_bytes_share")
JOINED = ("moe_expert_hbm_roofline", "moe_experts_touched_share",
          "moe_held_rows_share", "moe_held_rows_per_touched_expert",
          "device_idle_share.latency")
REDUCED = ["num_hidden_layers", "num_experts", "vocab_size",
           "max_position_embeddings"]
PERIOD = ["full_attention", "conv", "conv", "conv"]
# the catalog row's ``config`` (model-configs/architectures.jsonl,
# LFM2-24B-A2B), as the file must hold it but for REDUCED
CATALOG = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 11776,
    "layer_types": ["conv", "conv"] + PERIOD * 9 + ["full_attention", "conv"],
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1536, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_dense_layers": 2, "num_experts": 64,
    "num_experts_per_tok": 4, "num_hidden_layers": 40,
    "num_key_value_heads": 8,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 65536}


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
        CONFIG, "longdoc", 1)
    assert len(cell["why"]) <= 200
    assert arch.__name__ == "benchmark.architectures.lfm2_moe"
    assert all(hasattr(arch, name) for name in manifest.ARCHITECTURE_API)
    got = {m["name"] for m in manifest.metrics_of(man, "per_layer", CELL)}
    assert set(NEW_METRICS) | set(JOINED) <= got
    # the six that every cell reports
    assert {"decode_step_device_ms", "decode_hbm_roofline", "scheduler_host_share",
            "prefill_device_share", "load_s", "warm_s"} <= got
    assert {m["name"] for m in manifest.metrics_of(man, "end_to_end", CELL)} == {
        "tpot_p50_ms", "setup_s"}
    # no tokens_per_s, and so none of the metrics that move it (the two
    # prefill counters' shares among them): PERF.md section 6, PR 51
    assert not any(m["moves"] == "tokens_per_s"
                   for m in manifest.metrics_of(man, "per_layer", CELL))
    for name in NEW_METRICS:
        entry = next(m for m in man["per_layer"] if m["name"] == name)
        assert CELL in entry["workloads"] and entry["moves"] == "tpot_p50_ms"
        assert entry["unit"] == "%"
        assert callable(manifest.layer_reader(ROOT, man, name))
    for name in JOINED:
        entry = next(m for m in man["per_layer"] if m["name"] == name)
        assert CELL in entry["workloads"] and entry["moves"] == "tpot_p50_ms"
    assert cell in man["workloads"] and any(
        c["name"] == CONFIG for c in man["configs"])
    assert len(json.dumps(man)) < 64 << 10


def test_the_configuration_states_every_width_and_its_cut(man, cfg, arch):
    entry = next(c for c in man["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == cfg["reduced"] == REDUCED
    assert entry["source"] == cfg["source"]
    assert set(cfg["reduced_why"]) == set(REDUCED)
    for key in ("assumed", "deployment", "memory_arithmetic", "weights",
                "server", "server_why"):
        assert cfg[key], key
    # every key of the catalog row, unchanged but for the four in reduced
    assert len(CATALOG["layer_types"]) == 40
    assert {k: cfg[k] for k in CATALOG if k not in REDUCED} == {
        k: v for k, v in CATALOG.items() if k not in REDUCED}
    assert (cfg["num_hidden_layers"], cfg["num_experts"], cfg["vocab_size"],
            cfg["max_position_embeddings"]) == (13, 16, 16384, 16384)
    # the published counts and the share are stated beside the cut ones
    assert cfg["num_experts_published"] == CATALOG["num_experts"]
    assert cfg["num_hidden_layers_published"] == CATALOG["num_hidden_layers"]
    assert cfg["vocab_size_published"] == CATALOG["vocab_size"] == 4 * cfg["vocab_size"]
    assert cfg["max_position_embeddings_published"] == 128000
    assert cfg["experts_held"] == [0, 16] and arch.held(cfg) == (0, 16)
    assert cfg["served_layers"] == list(range(1, 14))
    assert cfg["server"] == {"slots": 64, "max_seq": 16384}
    for said in ("v5e-8", "two pipeline stages", "four chips"):
        assert said in cfg["deployment"], said
    # the floors: the dense layer once, whole periods and at least four
    # expert layers, at least 8 experts, an eighth of the vocabulary
    kinds = arch.served_layer_types(cfg)
    assert kinds == ["conv"] + PERIOD * 3
    assert arch.n_dense(cfg) == 1 and cfg["num_hidden_layers"] - 1 >= 4
    assert cfg["num_experts"] >= 8 and 8 * cfg["vocab_size"] >= 65536
    kw = arch.model_kwargs(cfg, 2**31 + 5)
    assert kw["seed"] < 2**31 and kw["block"] == "lfm2_moe"
    assert (kw["n_routed_experts"], kw["experts_held"], kw["experts_per_tok"],
            kw["route_scale"]) == (64, [0, 16], 4, 1.0)
    assert (kw["n_layers"], kw["max_seq"], kw["vocab_size"], kw["n_dense_layers"]) == (
        13, 16384, 16384, 1)
    assert (kw["d_model"], kw["n_heads"], kw["n_kv_heads"], kw["head_dim"],
            kw["d_ff"], kw["expert_width"], kw["conv_kernel"]) == (
        2048, 32, 8, 64, 11776, 1536, 3)
    assert (kw["rope_theta"], kw["norm_eps"], kw["layer_types"]) == (
        1e6, 1e-5, kinds)
    assert set(arch.rehearsal(cfg)) <= set(cfg)
    assert {"tie_word_embeddings", "embedding_norm", "head_dim", "qk_layernorm",
            "conv_in_proj_order", "conv_taps", "conv_tail", "router",
            "torch_dtype", "written_from"} <= set(cfg["assumed"])
    assert {"residual_scale", "matrices", "norms", "expert_bias"} <= set(
        cfg["weights"])
    for bad in (dict(cfg, experts_held=[0, 8]), dict(cfg, served_layers=[1, 2]),
                dict(cfg, served_layers=[2, 1, *range(3, 14)])):
        with pytest.raises(manifest.ManifestError):
            arch.model_kwargs(bad, 0)
    for key, value in (("use_expert_bias", False), ("conv_bias", True),
                       ("tie_word_embeddings", False), ("head_dim", 128)):
        with pytest.raises(manifest.ManifestError):
            arch.model_kwargs(dict(cfg, **{key: value}), 0)


def test_longdoc_is_the_traffic_the_issue_named(man, cfg):
    mix = manifest.traffic(ROOT, man, "longdoc")
    assert traffic.n_clients(mix, cfg["server"]["slots"]) == 64 + 8
    assert (mix["loop"], mix["ramp_s"], mix["drain_s"], mix["temperature"]) == (
        "closed", 30, 0, 0.0)
    assert mix["classes"] == [[1500, 1111, 2], [4100, 1723, 2],
                              [7700, 1429, 2], [12100, 2039, 2]]
    cycle = traffic.cycle(mix)
    assert sum(p for _k, p, _n in cycle) / len(cycle) == 6350
    assert sum(n for _k, _p, n in cycle) / len(cycle) == 1575.5
    ends = sorted({p + n for _k, p, n in cycle})
    assert ends == [2611, 5823, 9129, 14139] and ends[-1] <= cfg["server"]["max_seq"]
    assert all(p % 128 for p in traffic.prompt_lens(mix))


def test_costs_against_the_configs_own_arithmetic(cfg, arch):
    assert arch.expert_params(cfg) * arch.BYTES == 18_874_368         # 18.87 MB
    assert arch.kv_bytes_per_position_and_layer(cfg) == 2048
    assert arch.tail_bytes(cfg) == 8192
    assert arch.conv_params(cfg) == 16_783_360                        # 33.6 MB
    assert arch.attention_params(cfg) == 10_485_888                   # 21 MB
    # without the program's counters: nothing, not a guess
    assert arch.decode_step_bytes(cfg, 1000, {}) is None
    assert arch.kv_step_bytes(cfg, {"moe_layer_steps": 12}) is None
    assert arch.decode_attn_bytes(cfg, {}) is None
    steps = 50
    live = 64 * 7900
    counters = {"moe_layer_steps": 12 * steps,
                "moe_experts_touched": 12 * steps * 15.75,
                "conv_tails_written": 10 * 64 * steps,
                "kv_rows_live": 3 * steps * live,
                "kv_rows_read": int(3 * steps * live * 1.016)}
    none_live = arch.decode_step_bytes(cfg, 0, counters)
    experts = 12 * 15.75 * 18_874_368
    # outside the experts: 336 MB of convolution operators, 63 of
    # attention, 145 of dense FFN, 3 of routers, 67 of head; 10 MB of tails
    assert none_live - experts == pytest.approx(0.625e9, rel=0.01)
    assert experts == pytest.approx(3.57e9, rel=0.01)
    assert arch.decode_step_bytes(cfg, 1000, counters) - none_live == 1000 * 6144
    # ISSUE 51's step: 7.3 GB, of which the lanes' keys and values 42%
    mine, step = arch.kv_step_bytes(cfg, counters)
    assert step == pytest.approx(7.3e9, rel=0.01)
    assert step == arch.decode_step_bytes(cfg, live, counters)
    assert mine / step == pytest.approx(0.425, abs=0.01)
    assert arch.decode_attn_bytes(cfg, counters) == counters["kv_rows_read"] * 2048
    one = arch.prefill_flops(cfg, 6656, 1, {})
    assert arch.prefill_flops(cfg, 2 * 6656, 2, {}) == pytest.approx(2 * one)
    assert arch.prefill_flops(cfg, 0, 0, {}) == 0.0
    assert arch.prefill_attention_flops(cfg, 1792, 1) == pytest.approx(
        4 * 32 * 64 * 3 * 1792 * 1792 / 2)
    # the experts held here take a quarter of the picks: ~0.77 GFLOP a
    # token beside the attention's square
    per_token = (one - arch.prefill_attention_flops(cfg, 6656, 1)) / 6656
    assert 0.7e9 < per_token < 0.85e9


def _run(cfg, arch, counters, device_ops=(), modules=None):
    return {"config": cfg, "architecture": arch,
            "cell": {"name": "no-such-cell"},
            "peaks": {"hbm_bytes_per_s": 819e9},
            "trace": {"device_ops": [list(op) for op in device_ops],
                      "modules": modules or {}},
            "trace_counters": ({}, {"program": {"counters": counters}})}


def test_each_new_reader_on_a_fixture_and_without_its_counters(man, cfg, arch):
    read = {name: manifest.layer_reader(ROOT, man, name)
            for name in NEW_METRICS + JOINED[:4]}
    steps = 320
    live = 64 * 7900 * 3 * steps
    counters = {"moe_layer_steps": 12 * steps,
                "moe_experts_touched": int(12 * steps * 15.75),
                "moe_rows_routed": 12 * steps * 256, "moe_rows_held": 12 * steps * 64,
                "conv_tails_written": 640 * steps,
                "kv_rows_live": live, "kv_rows_read": int(live * 1.016)}
    ops = [("jit_fused_burst:touched_experts_ffn_f32_64_2048", 1.6),
           ("jit_fused_burst:ragged_decode_attention_bf16_64_4_8_128", 1.5),
           ("jit_fused_burst:fusion_kOutput_bf16_64_6144", 0.11),
           ("jit_fused_burst:fusion_bf16_64_2_2048", 0.01),
           ("jit_prefill_one:fusion_kOutput_bf16_1_8192_6144", 0.5),
           ("jit_fused_burst:fusion_kOutput_bf16_64_16384", 0.03)]
    modules = {"jit_fused_burst": {"runs": 40, "seconds": 3.6}}
    run = _run(cfg, arch, counters, ops, modules)
    # 1.016 x 64 x 7900 x 3 positions x 2,048 B a step at 819 GB/s over 1.5 s
    assert read["decode_attn_hbm_roofline"](run) == pytest.approx(
        100 * int(live * 1.016) * 2048 / 819e9 / 1.5)
    assert 60 < read["decode_attn_hbm_roofline"](run) < 100
    # x W_in's products, inside the burst alone: not the tails' update,
    # nor the prefill's product of the same width
    assert read["conv_in_proj_device_share"](run) == pytest.approx(
        100 * 0.11 / 3.6)
    assert read["kv_step_bytes_share"](run) == pytest.approx(42.5, abs=1.0)
    # the readers the cell joined size an expert and count from the file
    assert read["moe_held_rows_share"](run) == pytest.approx(25.0)
    assert read["moe_held_rows_per_touched_expert"](run) == pytest.approx(
        64 / 15.75, rel=0.01)
    assert read["moe_experts_touched_share"](run) == pytest.approx(
        100 * 15.75 / 16, rel=0.01)
    assert read["moe_expert_hbm_roofline"](run) == pytest.approx(
        100 * int(12 * steps * 15.75) * 18_874_368 / 819e9 / 1.6)
    # a program without the counters (the parent), or a trace without the
    # kernel: nothing, and no error
    for empty in (_run(cfg, arch, {}), _run(cfg, arch, {"tokens": 5}),
                  {**run, "trace_counters": None}):
        assert all(read[name](empty) is None for name in NEW_METRICS)
    assert read["decode_attn_hbm_roofline"](_run(cfg, arch, counters)) is None
    assert read["conv_in_proj_device_share"](
        _run(cfg, arch, counters, ops[:2], modules)) is None
    # another architecture's module has no such arithmetic: silent
    other = manifest.architecture(ROOT, man, "decoder")
    assert all(read[name](_run(cfg, other, counters, ops, modules)) is None
               for name in ("decode_attn_hbm_roofline", "kv_step_bytes_share"))


@pytest.fixture(scope="module")
def tiny(cfg, arch):
    small = dict(cfg, **arch.rehearsal(cfg), name="tiny")
    kw = arch.model_kwargs(small, 7)
    seed = kw.pop("seed")
    model = arch.SeededLfm2MoeLM(**kw)
    return model, model.init_params(seed)


@pytest.fixture
def tiny_batcher(tiny):
    """The comparison builds no batcher of its own: a test passes one, of
    the tiny cell's size (32 lanes as the comparison's batch, a cache of
    1024 positions: a prompt of 512 in the family's own 512 bucket)."""
    from seldon_core_tpu.serving.continuous import ContinuousBatcher

    model, params = tiny
    batcher = ContinuousBatcher(model, params, slots=32, max_seq=1024,
                                steps_per_poll=4)
    yield batcher
    batcher.close()


def test_the_served_model_agrees_with_the_reference_at_a_tiny_size(
        arch, tiny, tiny_batcher):
    import jax

    model, params = tiny
    assert type(model).__mro__[1].__name__ == "Lfm2MoeLM"
    assert all(a.dtype == jax.numpy.bfloat16
               for a in jax.tree_util.tree_leaves(params))
    # as the engine's batcher is warmed for its traffic: prompts of 100 and
    # 300, the longest context ending at 516 (the executables themselves
    # compile as they are called: ``warm`` is the scheduler's test's)
    tiny_batcher._warm_args = {"prompt_lens": (100, 300),
                               "max_new_tokens": 216, "batch_sizes": (1, 4, 8)}
    out = arch.compare_served(model, params, seed=2**31 + 3,
                              batcher=tiny_batcher)
    assert out["ok"] and out["ratio"] < arch.TOLERANCE, out
    # every lane from the batcher's own prefills in the buckets the traffic
    # pads to, 128 and 512, the lanes that share one in the rows a call the
    # scheduler gives them; the traffic's own lengths and the longest among
    # the lanes, their rows held to the reference's as the cache holds them
    assert out["prompt_len"] == 512 and {b for b, _m in out["prefill_calls"]} == {
        128, 512}
    assert {m for _b, m in out["prefill_calls"]} == {1, 4, 8}
    assert sum(m for _b, m in out["prefill_calls"]) == 28
    assert set(out["rows_ratio_lanes"]) == {"100", "300", "512"}
    assert max(out["rows_ratio_lanes"].values()) <= arch.ROWS_TOLERANCE
    assert out["prefill_margin"] <= arch.TOLERANCE
    assert out["picks_margin"] <= arch.PICKS_MARGIN and out["picks_agree"] > 0.9
    assert out["rows_ratio"] <= arch.ROWS_TOLERANCE
    assert out["tails_ratio"] <= arch.TAILS_TOLERANCE
    assert out["weights_err"] <= arch.WEIGHTS_TOLERANCE
    # the burst's batch, most lanes live: 28 lanes x 4 steps (the batcher's
    # own burst length) and the prefill's last
    assert (out["lanes"], out["lanes_live"], out["positions"],
            out["decode_steps"]) == (32, 28, 113, 4)
    assert (out["cache_len"], out["bucket"], out["borrowed"]) == (1024, 512, False)
    assert out["counters_are_the_picks"] and out["burst_counters_hold"]
    assert out["idle_untouched"] and out["inserted"]
    # 28 lanes x 4 picks of 16 experts, 4 held: all touched, a quarter lands
    assert out["experts_touched_a_layer_step"] == 4
    assert 0.15 < out["held_rows_share"] < 0.35


def test_the_comparisons_lanes_are_the_cells(man, cfg, arch):
    """The comparison's batch is the configuration's under the cell's
    traffic: most lanes live, every eighth idle, lengths spread to where
    the mix's longest contexts end, the mix's own prompt lengths among them
    (one in each bucket its prompts pad to), lanes at the kernel's block
    edges and on both sides of the batcher's last bucket, and no two
    stepping at one position."""

    assert cfg["server"]["slots"] == 64
    mix = manifest.traffic(ROOT, man, "longdoc")
    asked = tuple(sorted(set(traffic.prompt_lens(mix))))
    end = max(asked) + traffic.max_new(mix)
    assert (asked, end) == ((1500, 4100, 7700, 12100), 14139)
    start = arch.lane_lengths(64, end - 8, 8, asked)
    lens = sorted(start.values())
    assert len(start) == 56 and set(range(64)) - set(start) == set(range(5, 64, 8))
    assert lens[0] == 883 and lens[-1] + 8 == end and set(asked) < set(lens)
    assert {n % arch.READ_BLOCK for n in lens} >= {0, 1, arch.READ_BLOCK - 1}
    assert {arch.BUCKET_EDGE, arch.BUCKET_EDGE + 8 + 1} <= set(lens)
    assert min(b - a for a, b in zip(lens, lens[1:])) >= 8
    # a lane in every bucket the mix's prompts pad to, and past the last
    for lo, hi in ((0, 1792), (1792, 4608), (4608, 8192), (8192, 12288),
                   (12288, end)):
        assert any(lo < n <= hi for n in lens)
    with pytest.raises(ValueError):
        arch.lane_lengths(64, 100, 4)


@pytest.mark.parametrize("variant", [
    "weights_8bit", "bias_in_weights", "taps_reversed", "no_qk_norm",
    "rope_theta_1e4", "tail_at_bucket_end"])
def test_a_wrong_reference_is_not_agreed_with(arch, tiny, tiny_batcher,
                                              variant):
    model, params = tiny
    out = arch.compare_served(model, params, seed=2**31 + 3, prompt_len=512,
                              variant=variant, batcher=tiny_batcher)
    assert not out["ok"], out
    assert (out["ratio"] > arch.TOLERANCE or out["picks_margin"] > arch.PICKS_MARGIN
            or out["rows_ratio"] > arch.ROWS_TOLERANCE
            or out["tails_ratio"] > arch.TAILS_TOLERANCE
            or out["weights_err"] > arch.WEIGHTS_TOLERANCE), out
    if variant == "tail_at_bucket_end":
        assert out["ratio"] <= arch.TOLERANCE < out["tails_ratio_insert"]
    with pytest.raises(ValueError):
        arch.compare_served(model, params, seed=1, prompt_len=512,
                            variant="no_such_model", batcher=tiny_batcher)


def test_a_burst_that_leaves_a_live_lane_out_is_not_agreed_with(
        arch, tiny, tiny_batcher):
    """The burst's own control: its tokens, its rows, its tails and its
    counters are held to the step's, and the reference alone would not see
    it."""
    model, params = tiny
    out = arch.compare_served(model, params, seed=2**31 + 3, prompt_len=512,
                              variant="burst_idles_a_lane",
                              batcher=tiny_batcher)
    assert not out["ok"], out
    assert out["ratio"] <= arch.TOLERANCE and out["rows_ratio"] <= arch.ROWS_TOLERANCE
    assert out["burst_rows_ratio"] > arch.BURST_TOLERANCE
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
        arch.compare_served(model, params, seed=11)
    batcher = ContinuousBatcher(model, params, slots=8, max_seq=1024,
                                steps_per_poll=4)
    try:
        assert arch._serving_batcher(params) is batcher
        out = arch.compare_served(model, params, seed=11)
        assert out["ok"] and out["borrowed"], out
        assert (out["lanes"], out["cache_len"], out["prompt_len"]) == (8, 1024, 512)
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
        if m["name"] in NEW_METRICS + JOINED[:4]:
            m["workloads"].append(CONFIG + ".tiny")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(man))
    out, line = _rehearse(tmp_path, CONFIG + ".tiny", "2")
    assert line["correct"] and line["failed"] == 0 and line["attempted"] > 0
    assert "'rows_ratio'" in out and "'tails_ratio'" in out
    assert "'borrowed': True" in out
    got = line["metrics"]
    assert {"tpot_p50_ms", "setup_s", "decode_step_device_ms",
            "decode_hbm_roofline", "scheduler_host_share", "prefill_device_share",
            "load_s", "warm_s"} <= set(got)
    # 4 of 16 experts held, 4 picks a live lane
    assert 5.0 < got["moe_held_rows_share"]["value"] < 60.0
    assert got["moe_held_rows_per_touched_expert"]["value"] >= 1.0
    assert 0.0 < got["moe_experts_touched_share"]["value"] <= 100.0
    assert 0.0 < got["kv_step_bytes_share"]["value"] < 100.0
    # the kernels run on a TPU only: their readers find nothing here
    assert "decode_attn_hbm_roofline" not in got
    assert "moe_expert_hbm_roofline" not in got
    run_dir, = (bench / "_runs" / (CONFIG + ".tiny")).glob("*-trace2-0")
    served = json.load(open(run_dir / "model" / "jax_config.json"))
    assert served["family"] == "benchmark_lfm2_moe"
    assert served["config"]["block"] == "lfm2_moe"
    counters = json.load(open(run_dir / "capture.json"))["counters"]
    assert counters["conv_tails_written"] > 0 and counters["moe_rows_held"] > 0
    assert 0 < counters["kv_rows_live"] <= counters["kv_rows_read"]
    assert counters["moe_prefill_pairs_routed"] > 0
