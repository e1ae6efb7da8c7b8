"""The sdar_moe architecture's benchmark files hold what the others' hold:
the manifest finds them, the configuration states every catalog key and
its cut, the mix is the issue's and the same work for every seed, the
costs are the file's own arithmetic, each new reader reads a fixture and
falls silent without its counters or ops, the served model agrees with the
plain reference at a tiny size through a batcher's own executables and six
wrong ones do not, and the tiny CPU rehearsal runs the configuration end to
end. CPU only.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_sdar_moe.py -q
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
    _copy_of_the_benchmark, _rehearse)

CELL = "sdar-30b-a3b.blockgen"
NEW_METRICS = ("block_forwards_per_token", "block_attn_hbm_roofline",
               "block_unmask_device_share")
# accepted metrics whose lists the cell joined: their readers find their
# counters, their kernel and the trace's window as they were
JOINED = ("device_idle_share.latency", "moe_experts_touched_share",
          "moe_rows_per_touched_expert", "moe_expert_hbm_roofline")
# the catalog row's config (model-configs/architectures.jsonl), every key
CATALOG = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 32768, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "sdar_moe",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 151936}


@pytest.fixture(scope="module")
def man():
    return manifest.load(ROOT)


@pytest.fixture(scope="module")
def cfg(man):
    return manifest.config(ROOT, man, "sdar-30b-a3b")


@pytest.fixture(scope="module")
def arch(man, cfg):
    return manifest.architecture(ROOT, man, cfg["architecture"])


def test_the_cell_its_files_and_its_metrics_are_found(man, cfg, arch):
    cell = manifest.cell(man, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "sdar-30b-a3b", "blockgen", 1)
    assert man["workloads"][-1] is cell and man["configs"][-1]["name"] == "sdar-30b-a3b"
    assert arch.__name__ == "benchmark.architectures.sdar_moe"
    got = [m["name"] for m in manifest.metrics_of(man, "per_layer", CELL)]
    # the six all-cell ones, a pass read as a step; the device's idle share
    # and the three expert metrics whose readers find what they read as it
    # was (the burst's executable is ``jit_fused_burst`` here too; the rows
    # a touched expert is read for are one quantity under one name, a
    # block's four rows a lane or a step's one); the three new ones
    assert got == ["decode_step_device_ms", "decode_hbm_roofline",
                   "device_idle_share.latency", "load_s", "warm_s",
                   "scheduler_host_share", "prefill_device_share",
                   "moe_experts_touched_share",
                   "moe_rows_per_touched_expert", "moe_expert_hbm_roofline",
                   *NEW_METRICS]
    assert {m["name"] for m in manifest.metrics_of(man, "end_to_end", CELL)} == {
        "tpot_p50_ms", "setup_s"}
    assert [m["name"] for m in man["per_layer"][-3:]] == list(NEW_METRICS)
    for name in JOINED:
        entry = next(m for m in man["per_layer"] if m["name"] == name)
        assert entry["workloads"][-1] == CELL and entry["moves"] == "tpot_p50_ms"
    for name in NEW_METRICS:
        entry = next(m for m in man["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [CELL] and entry["moves"] == "tpot_p50_ms"
        assert callable(manifest.layer_reader(ROOT, man, name))


def test_the_configuration_states_every_catalog_key_and_its_cut(man, cfg, arch):
    entry = next(c for c in man["configs"] if c["name"] == "sdar-30b-a3b")
    assert entry["source"] == cfg["source"] == (
        "https://huggingface.co/JetLM/SDAR-30B-A3B-Chat/blob/main/config.json")
    assert entry["reduced"] == cfg["reduced"] == [
        "num_hidden_layers", "max_position_embeddings"]
    assert set(cfg["reduced_why"]) == set(cfg["reduced"])
    differs = {k for k, v in CATALOG.items() if cfg[k] != v}
    assert differs == set(cfg["reduced"])
    assert (cfg["num_hidden_layers"], cfg["served_layers"]) == (6, [0, 1, 2, 3, 4, 5])
    assert cfg["max_position_embeddings"] == cfg["server"]["max_seq"] == 4096
    assert cfg["server"]["slots"] == 32
    for key in ("assumed", "deployment", "memory_arithmetic", "weights",
                "generation"):
        assert cfg[key], key
    assert {"block_length", "denoising_steps", "no_logit_shift", "remasking",
            "mask_token_id", "q_norm_k_norm", "torch_dtype", "written_from"} <= set(
                cfg["assumed"])
    gen = cfg["generation"]
    assert (gen["block_length"], gen["denoising_steps"], gen["remasking"],
            gen["mask_token_id"]) == (4, 2, "low_confidence_static", 151669)
    assert gen["mask_token_id"] < cfg["vocab_size"] and gen["why"]
    kw = arch.model_kwargs(cfg, 2**31 + 5)
    assert kw["seed"] < 2**31 and kw["block"] == "sdar_moe"
    assert (kw["n_layers"], kw["max_seq"], kw["d_model"]) == (6, 4096, 2048)
    assert (kw["n_routed_experts"], kw["experts_per_tok"], kw["expert_width"]) == (
        128, 8, 768)
    assert (kw["block_length"], kw["denoising_steps"]) == (4, 2)
    assert set(arch.rehearsal(cfg)) <= set(cfg)
    with pytest.raises(manifest.ManifestError):
        arch.model_kwargs(dict(cfg, norm_topk_prob=False), 1)


def test_blockgen_is_the_issues_mix_and_the_same_work_for_every_seed(man):
    mix = manifest.traffic(ROOT, man, "blockgen")
    assert mix["classes"] == [[250, 622, 2], [701, 1012, 2], [1283, 766, 2],
                              [1900, 1396, 2]]
    assert (mix["loop"], mix["clients"], mix["ramp_s"], mix["drain_s"]) == (
        "closed", {"per_slot": 1, "extra": 8}, 20, 0)
    assert mix["temperature"] == 0.0 and traffic.n_clients(mix, 32) == 40
    assert traffic.mean_prompt(mix) == 1033.5
    cycle = traffic.cycle(mix)
    assert sum(new for _k, _p, new in cycle) / len(cycle) == 949
    assert [p % 4 for p, _n, _c in mix["classes"]] == [2, 1, 3, 0]
    assert sorted(p + n for p, n, _c in mix["classes"]) == [872, 1713, 2049, 3296]
    n = len(cycle)
    for seed in (1, 2**31 + 12345):
        for c in range(3):
            got = [traffic.request_class(mix, seed, c * n + j) for j in range(n)]
            assert sorted(got) == sorted(cycle)


def test_costs_against_the_configs_own_arithmetic(cfg, arch):
    assert arch.expert_params(cfg) * arch.BYTES == 9_437_184          # 9.44 MB
    assert arch.kv_bytes_per_position_and_layer(cfg) == 2048
    assert arch.decode_step_bytes(cfg, 1000, {}) is None
    assert arch.block_attn_bytes(cfg, {}) is None
    counters = {"moe_layer_steps": 6 * 500, "moe_experts_touched": 6 * 500 * 127.9}
    none_live = arch.decode_step_bytes(cfg, 0, counters)
    experts = 6 * 127.9 * 9_437_184
    # outside the experts: the head 0.62 GB, attention and routers 0.23 GB
    assert none_live - experts == pytest.approx(0.852e9, rel=0.01)
    # a live position: 2048 B in each of six layers
    assert arch.decode_step_bytes(cfg, 1000, counters) - none_live == pytest.approx(
        1000 * 6 * 2048)
    assert arch.block_attn_bytes(cfg, {"block_rows_read": 1000}) == 2_048_000
    one = arch.prefill_flops(cfg, 4096, 1, {})
    per_token = 2 * 6 * (18_874_368 + 2048 * 128 + 8 * 4_718_592)
    assert one == pytest.approx(
        per_token * 4096 + 6 * 4 * 4096 * 4096 ** 2 / 2 + 2 * 2048 * 151936)
    assert arch.prefill_flops(cfg, 2 * 4096, 2, {}) == pytest.approx(2 * one)
    assert arch.prefill_flops(cfg, 4096 + 512, 2, {}) < one + arch.prefill_flops(
        cfg, 512, 1, {})
    assert arch.prefill_flops(cfg, 0, 0, {}) == 0.0


def _run(cfg, arch, counters, device_ops=(), modules=None):
    return {"config": cfg, "architecture": arch,
            "peaks": {"hbm_bytes_per_s": 819e9},
            "trace": {"device_ops": [list(op) for op in device_ops],
                      "modules": modules or {}},
            "trace_counters": ({}, {"program": {"counters": counters}})}


def test_each_new_reader_on_a_fixture_and_without_its_counters(man, cfg, arch):
    read = {name: manifest.layer_reader(ROOT, man, name) for name in NEW_METRICS}
    rows_each = manifest.layer_reader(ROOT, man, "moe_rows_per_touched_expert")
    counters = {"tokens": 12_000, "block_forwards": 9_100,
                "block_rows_read": 1_200_000, "moe_rows_routed": 1_740_000,
                "moe_experts_touched": 218_000}
    ops = [("jit_fused_burst:block_decode_attention_bf16_32_4_32_128", 0.4),
           ("jit_fused_burst:fusion_kOutput_bf16_128_151936", 0.30),
           ("jit_fused_burst:fusion_f32_128_151936", 0.10),
           ("jit_fused_burst:touched_experts_ffn_f32_128_2048", 2.0),
           ("jit_prefill_one:fusion_f32_1_151936", 9.0)]
    modules = {"jit_fused_burst": {"runs": 40, "seconds": 3.2}}
    run = _run(cfg, arch, counters, ops, modules)
    assert read["block_forwards_per_token"](run) == pytest.approx(9100 / 12000)
    # the accepted reader, on a block pass's counters as on a step's
    assert rows_each(run) == pytest.approx(1740 / 218)
    # 1.2 M rows x 2048 B at 819 GB/s is 3.0 ms of the kernel's 0.4 s
    assert read["block_attn_hbm_roofline"](run) == pytest.approx(
        100 * 1_200_000 * 2048 / 819e9 / 0.4)
    # the burst's ops as wide as the vocabulary, not a prefill's
    assert read["block_unmask_device_share"](run) == pytest.approx(100 * 0.4 / 3.2)
    # a program without the counters (the parent's, another family's), or a
    # trace without the ops: nothing, and no raise
    other = {"tokens": 5, "moe_rows_routed": 10, "moe_experts_touched": 5}
    for empty in (_run(cfg, arch, {}), _run(cfg, arch, other, ops, modules),
                  {**run, "trace_counters": None}):
        assert all(reader(empty) is None for reader in read.values())
    bare = _run(cfg, arch, counters, (), modules)
    assert read["block_attn_hbm_roofline"](bare) is None
    assert read["block_unmask_device_share"](bare) is None
    # another architecture's module sizes no block rows
    assert read["block_attn_hbm_roofline"](
        {**run, "architecture": manifest}) is None


@pytest.fixture(scope="module")
def tiny(cfg, arch):
    """The rehearsal's sizes at 16 lanes, through a batcher of its own."""
    from seldon_core_tpu.serving.continuous import ContinuousBatcher

    small = dict(cfg, **arch.rehearsal(cfg), name="tiny")
    small["server"] = {"slots": 16, "max_seq": 512}
    kw = arch.model_kwargs(small, 7)
    seed = kw.pop("seed")
    model = arch.SeededSdarMoeLM(**kw)
    params = model.init_params(seed)
    batcher = ContinuousBatcher(model, params, slots=16, max_seq=512,
                                prefill_buckets=(64, 256), steps_per_poll=8)
    return model, batcher, arch.serve(model, batcher.params, 2**31 + 3, batcher,
                                      top=400)


def test_the_served_model_agrees_with_the_reference_at_a_tiny_size(arch, tiny):
    import jax

    model, batcher, served = tiny
    assert type(model).__mro__[1].__name__ == "SdarMoeLM"
    assert all(a.dtype == jax.numpy.bfloat16
               for a in jax.tree_util.tree_leaves(batcher.params))
    out = arch.judge(model, served, batcher.params)
    assert out["ok"] and out["ratio"] < arch.TOLERANCE, out
    assert out["picks_margin"] <= arch.PICKS_MARGIN and out["picks_agree"] > 0.9
    assert max(out["rows_ratio"], out["prompt_rows_ratio"]) < arch.ROWS_TOLERANCE
    # 14 live lanes of 16, 8 passes each, every lane past a commit or two
    assert (out["lanes"], out["lanes_live"], out["passes"]) == (16, 14, 8)
    assert out["block_forwards"] == 14 * 8 and out["commits"] >= 2 * 14
    assert out["counters_hold"] and out["registers_hold"]
    assert out["burst_margin"] <= arch.BURST_TOLERANCE
    assert out["burst_rows_ratio"] <= arch.BURST_ROWS_TOLERANCE
    # 14 lanes x 4 rows x 2 picks of 8 experts: most touched (a block's
    # masked rows share one embedding and route alike), 14 rows or more on each
    assert 4 < out["experts_touched_a_layer_pass"] <= 8
    assert out["rows_per_touched_expert"] >= 14


def test_the_comparisons_lanes_are_the_cells(cfg, arch):
    """Every remainder on both sides of the kernel's block edge and of a
    later one, the cell's four prompt lengths, every eighth lane idle."""
    start = arch.lane_lengths(cfg["server"]["slots"], 3300, 4, 20)
    lens = sorted(start.values())
    assert len(start) == 28 and set(range(32)) - set(start) == {5, 13, 21, 29}
    assert len(set(lens)) == 28 and lens[-1] == 3280
    assert {126, 127, 128, 129, 130, 131, 1, 2, 3} <= set(lens)
    assert {250, 701, 1283, 1900} <= set(lens)
    assert {n % 4 for n in lens if n > 1500} == {0, 1, 2, 3}


@pytest.mark.parametrize("variant", [
    "weights_8bit", "mask_causal", "blocks_from_prompt_end", "no_qk_norm",
    "rope_theta_1e4", "no_commit"])
def test_a_wrong_reference_is_not_agreed_with(arch, tiny, variant):
    """The six controls: each fails a limit. The block mask made causal and
    the blocks counted from the prompt's end show in the logits; a commit
    left out in the logits of the blocks after it and in the rows."""
    from benchmark.reference import sdar_moe as reference

    assert variant in reference.VARIANTS
    model, batcher, served = tiny
    out = arch.judge(model, served, batcher.params, variant)
    assert not out["ok"], out
    assert out["ratio"] > arch.TOLERANCE or out["rows_ratio"] > arch.ROWS_TOLERANCE
    if variant == "no_commit":
        assert out["rows_ratio"] > 10 * arch.ROWS_TOLERANCE
        assert out["prompt_rows_ratio"] < arch.ROWS_TOLERANCE


@pytest.mark.parametrize("fault", ["burst_idles_a_lane", "burst_weights_8bit"])
def test_a_wrong_burst_is_not_agreed_with(arch, tiny, fault):
    """The timed executable itself made wrong, the pass and the reference
    left sound: a live lane left out fails by its own check; weights
    rounded to 8 bits in the burst alone fail the rows it left in the cache
    against the reference's, and what ties the burst to the pass whose
    logits are compared (the tokens it filled in, the rows)."""
    assert fault in arch.BURST_FAULTS
    model, batcher, _served = tiny
    out = arch.compare_served(model, batcher.params, 2**31 + 3, variant=fault,
                              batcher=batcher, top=400)
    assert not out["ok"], out
    if fault == "burst_idles_a_lane":
        assert out["lanes_active"] == out["lanes_live"] - 1
    else:
        assert out["lanes_active"] == out["lanes_live"]
        assert out["rows_ratio"] > arch.ROWS_TOLERANCE
        assert out["burst_rows_ratio"] > arch.BURST_ROWS_TOLERANCE
        assert out["burst_margin"] > arch.BURST_TOLERANCE
        # the pass and the reference ran on the sound weights
        assert out["ratio"] < arch.TOLERANCE


def test_the_configuration_is_rehearsed_end_to_end(tmp_path):
    """The cell's configuration under a tiny mix in a copy (the cell's own
    holds 1,900-token prompts and 1,396-token outputs): served through the
    engine by the module's family, compared by its ``compare_served``, and
    the program's counters reach the new metrics."""
    bench, man = _copy_of_the_benchmark(tmp_path)
    mix = {"loop": "closed", "clients": {"per_slot": 1, "extra": 1},
           "ramp_s": 1, "drain_s": 0, "temperature": 0.0,
           "classes": [[21, 9, 1], [42, 14, 1], [19, 6, 1]]}
    (bench / "traffic" / "tinyblocks.json").write_text(json.dumps(mix))
    man["workloads"].append({"name": "sdar-30b-a3b.tinyblocks",
                             "config": "sdar-30b-a3b", "traffic": "tinyblocks",
                             "chips": 1, "why": "test"})
    for m in man["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"].append("sdar-30b-a3b.tinyblocks")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(man))
    out, line = _rehearse(tmp_path, "sdar-30b-a3b.tinyblocks", "2")
    assert line["correct"] and line["failed"] == 0 and line["attempted"] > 0
    assert "'picks_margin'" in out and "'rows_ratio'" in out
    got = line["metrics"]
    assert {"tpot_p50_ms", "setup_s", "decode_step_device_ms",
            "decode_hbm_roofline", "scheduler_host_share", "prefill_device_share",
            "load_s", "warm_s"} <= set(got)
    # two denoising passes and a commit a block of 4, fewer where a block
    # holds a prompt's tail, more where a budget ends inside one
    assert 0.5 < got["block_forwards_per_token"]["value"] < 1.2
    # the kernel runs on a TPU only, and the CPU's ops carry no shapes
    assert "block_attn_hbm_roofline" not in got
    run_dir, = (bench / "_runs" / "sdar-30b-a3b.tinyblocks").glob("*-trace2-0")
    served = json.load(open(run_dir / "model" / "jax_config.json"))
    assert served["family"] == "benchmark_sdar_moe"
    assert served["config"]["block"] == "sdar_moe"
    counters = json.load(open(run_dir / "capture.json"))["counters"]
    assert counters["block_forwards"] > counters["block_commit_forwards"] > 0
    assert counters["block_tokens_unmasked"] > 0 and counters["block_rows_read"] > 0
    # the accepted metrics the cell joined read what they read here too
    assert 0.0 < got["moe_experts_touched_share"]["value"] <= 100.0
    assert got["moe_rows_per_touched_expert"]["value"] >= 1.0
    assert 0.0 <= got["device_idle_share.latency"]["value"] <= 100.0
