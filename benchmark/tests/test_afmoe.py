"""The afmoe architecture's benchmark files hold what the decoder's hold:
the manifest finds them, the configuration states its cut, the mix is the
same work for every seed, the costs are the file's own arithmetic, each new
reader reads a fixture and falls silent without its counters, the served
model agrees with the plain reference at a tiny size and a wrong one does
not, and the tiny CPU rehearsal runs the configuration end to end. CPU only.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_afmoe.py -q
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

CELL = "trinity-mini.longbatch"
NEW_METRICS = ("moe_experts_touched_share", "moe_rows_per_touched_expert",
               "moe_expert_hbm_roofline", "kv_window_read_share")


@pytest.fixture(scope="module")
def man():
    return manifest.load(ROOT)


@pytest.fixture(scope="module")
def cfg(man):
    return manifest.config(ROOT, man, "trinity-mini")


@pytest.fixture(scope="module")
def arch(man, cfg):
    return manifest.architecture(ROOT, man, cfg["architecture"])


def test_the_cell_its_files_and_its_metrics_are_found(man, cfg, arch):
    cell = manifest.cell(man, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "trinity-mini", "longbatch", 1)
    assert arch.__name__ == "benchmark.architectures.afmoe"
    got = {m["name"] for m in manifest.metrics_of(man, "per_layer", CELL)}
    assert set(NEW_METRICS) <= got
    # the universal ones read in the cell too; the lists of the others do
    # not hold it
    assert {"decode_step_device_ms", "decode_hbm_roofline", "scheduler_host_share",
            "prefill_device_share", "load_s", "warm_s"} <= got
    # tokens_per_s and its two layer metrics list the cell: its two sets of
    # six read spreads of 0.10% and 0.23% at 99.3% occupancy (PERF.md, PR 33)
    assert {m["name"] for m in manifest.metrics_of(man, "end_to_end", CELL)} == {
        "tpot_p50_ms", "tokens_per_s", "setup_s"}
    assert {"lane_occupancy", "device_idle_share.batch"} <= got
    for name in NEW_METRICS:
        entry = next(m for m in man["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [CELL] and entry["moves"] == "tpot_p50_ms"
        assert callable(manifest.layer_reader(ROOT, man, name))


def test_the_configuration_states_every_width_and_its_cut(man, cfg, arch):
    entry = next(c for c in man["configs"] if c["name"] == "trinity-mini")
    assert entry["reduced"] == cfg["reduced"] == [
        "num_hidden_layers", "max_position_embeddings"]
    assert set(cfg["reduced_why"]) == set(cfg["reduced"])
    for key in ("assumed", "deployment", "memory_arithmetic", "weights", "server"):
        assert cfg[key], key
    published = {
        "hidden_size": 2048, "num_attention_heads": 32, "num_key_value_heads": 4,
        "head_dim": 128, "intermediate_size": 6144, "moe_intermediate_size": 1024,
        "num_experts": 128, "num_experts_per_tok": 8, "num_shared_experts": 1,
        "num_dense_layers": 2, "sliding_window": 2048, "vocab_size": 200192,
        "rope_theta": 10000, "route_scale": 2.826, "score_func": "sigmoid",
        "global_attn_every_n_layers": 4, "rms_norm_eps": 1e-05}
    assert {k: cfg[k] for k in published} == published
    assert len(cfg["layer_types"]) == 32      # the published list, whole
    assert cfg["layer_types"] == (["sliding_attention"] * 3 + ["full_attention"]) * 8
    kw = arch.model_kwargs(cfg, 2**31 + 5)
    assert kw["seed"] < 2**31 and kw["block"] == "afmoe"
    assert kw["layer_types"] == ["sliding_attention"] * 5 + ["full_attention"]
    assert (kw["n_layers"], kw["n_dense_layers"], kw["max_seq"]) == (6, 2, 4096)
    assert (kw["n_heads"] * kw["head_dim"], kw["d_model"]) == (4096, 2048)
    assert set(arch.rehearsal(cfg)) <= set(cfg)
    assert {"four_norms", "q_norm_k_norm", "output_gate", "embedding_scale",
            "rotary_on_window_layers_only", "bias_in_selection_only"} <= set(
                cfg["assumed"])


def test_longbatch_is_the_issues_mix_and_the_same_work_for_every_seed(man):
    mix = manifest.traffic(ROOT, man, "longbatch")
    assert mix["classes"] == [[128, 384, 2], [512, 512, 2], [2048, 384, 2],
                              [3328, 512, 2]]
    assert (mix["loop"], mix["clients"], mix["ramp_s"], mix["drain_s"]) == (
        "closed", {"per_slot": 1, "extra": 8}, 5, 0)
    assert mix["temperature"] == 0.0 and traffic.n_clients(mix, 32) == 40
    assert traffic.mean_prompt(mix) == 1504
    n = len(traffic.cycle(mix))
    want = sorted(traffic.cycle(mix))
    for seed in (1, 2**31 + 12345):
        for c in range(3):
            got = [traffic.request_class(mix, seed, c * n + j) for j in range(n)]
            assert sorted(got) == want
    assert max(p + new for _k, p, new in traffic.cycle(mix)) <= 4096


def test_costs_against_the_configs_own_arithmetic(cfg, arch):
    assert arch.expert_params(cfg) * arch.BYTES == 12_582_912        # 12.58 MB
    assert arch.kv_bytes_per_position_and_layer(cfg) == 2048
    # without the program's counters: nothing, not a guess
    assert arch.decode_step_bytes(cfg, 1000, {}) is None
    counters = {"moe_layer_steps": 400, "moe_experts_touched": 400 * 111.7,
                "kv_positions_live_window": 1000, "kv_positions_seen_window": 600}
    none_live = arch.decode_step_bytes(cfg, 0, counters)
    experts = 4 * 111.7 * 12_582_912
    # what lies outside the experts: head 0.82, attention 0.33, dense FFNs
    # 0.15, shared 0.05 GB and the routers and norms
    assert none_live - experts == pytest.approx(1.354e9, rel=0.01)
    # a live position: 2048 B in the full layer, 0.6 x 2048 B in each of 5
    assert arch.decode_step_bytes(cfg, 1000, counters) - none_live == pytest.approx(
        1000 * 2048 * (1 + 5 * 0.6))
    one = arch.prefill_flops(cfg, 4096, 1, {})
    per_token = 2 * (6 * 27_262_976 + 2 * 3 * 2048 * 6144
                     + 4 * (2048 * 128 + 9 * 6_291_456))
    band = 2048 * 4096 - 2048 * 2048 / 2
    assert one == pytest.approx(
        per_token * 4096 + 4 * 4096 * (4096 ** 2 / 2 + 5 * band)
        + 2 * 2048 * 200192)
    assert arch.prefill_flops(cfg, 2 * 4096, 2, {}) == pytest.approx(2 * one)
    # unequal lengths are counted low, never high
    assert arch.prefill_flops(cfg, 4096 + 512, 2, {}) < one + arch.prefill_flops(
        cfg, 512, 1, {})
    assert arch.prefill_flops(cfg, 0, 0, {}) == 0.0


def _run(cfg, arch, counters, device_ops=()):
    return {"config": cfg, "architecture": arch,
            "peaks": {"hbm_bytes_per_s": 819e9},
            "trace": {"device_ops": [list(op) for op in device_ops]},
            "trace_counters": ({}, {"program": {"counters": counters}})}


def test_each_new_reader_on_a_fixture_and_without_its_counters(man, cfg, arch):
    read = {name: manifest.layer_reader(ROOT, man, name) for name in NEW_METRICS}
    counters = {"moe_layer_steps": 1000, "moe_experts_touched": 111_500,
                "moe_rows_routed": 256_000, "kv_positions_read_window": 1408,
                "kv_positions_seen_window": 1300, "kv_positions_live_window": 1792}
    kernel = "jit_fused_burst:touched_experts_ffn_f32_32_2048"
    run = _run(cfg, arch, counters, [(kernel, 1.9), ("jit_prefill_one:x", 9.0)])
    assert read["moe_experts_touched_share"](run) == pytest.approx(100 * 111.5 / 128)
    assert read["moe_rows_per_touched_expert"](run) == pytest.approx(256 / 111.5)
    assert read["kv_window_read_share"](run) == pytest.approx(100 * 1408 / 1792)
    # 111,500 experts x 12.58 MB at 819 GB/s is 1.713 s of the kernel's 1.9
    assert read["moe_expert_hbm_roofline"](run) == pytest.approx(
        100 * 111_500 * 12_582_912 / 819e9 / 1.9)
    # a program without the counters, or a trace without the kernel: nothing
    for empty in (_run(cfg, arch, {}), _run(cfg, arch, {"tokens": 5}),
                  {**run, "trace_counters": None}):
        assert all(reader(empty) is None for reader in read.values())
    assert read["moe_expert_hbm_roofline"](_run(cfg, arch, counters)) is None


@pytest.fixture(scope="module")
def tiny(cfg, arch):
    small = dict(cfg, **arch.rehearsal(cfg), name="tiny")
    kw = arch.model_kwargs(small, 7)
    seed = kw.pop("seed")
    model = arch.SeededAfmoeLM(**kw)
    return model, model.init_params(seed)


def test_the_served_model_agrees_with_the_reference_at_a_tiny_size(arch, tiny):
    import jax

    model, params = tiny
    assert type(model).__mro__[1].__name__ == "AfmoeLM"
    assert all(a.dtype == jax.numpy.bfloat16
               for a in jax.tree_util.tree_leaves(params))
    out = arch.compare_served(model, params, seed=2**31 + 3, prompt_len=640,
                              decode_steps=3)
    assert out["ok"] and out["ratio"] < arch.TOLERANCE, out
    assert out["picks_margin"] <= arch.PICKS_MARGIN and out["picks_agree"] > 0.9
    # the burst's batch, most lanes live: 28 lanes x 3 steps and the prefill's last
    assert (out["lanes"], out["lanes_live"], out["positions"]) == (32, 28, 85)
    assert out["counters_are_the_picks"]
    # 28 lanes x 2 picks of 8 experts: nearly all touched, 7 rows on each
    assert 7.5 < out["experts_touched_a_layer_step"] <= 8
    assert 7 <= out["rows_per_touched_expert"] < 7.5


def test_the_comparisons_lanes_are_the_cells(cfg, arch):
    """The comparison's batch is the configuration's: its lanes lie on both
    sides of the window, one crosses its edge while it steps, one goes on
    where the prefill ended, and no two step at one position."""
    assert arch.LANES == cfg["server"]["slots"]
    start = arch.lane_lengths(2304, 4, cfg["sliding_window"])
    lens = sorted(start.values())
    assert len(start) == 28 and set(range(32)) - set(start) == {5, 13, 21, 29}
    assert lens[0] == 128 and lens[-1] == 2304 and 2046 in lens
    assert sum(n + 4 <= 2048 for n in lens) >= 20 and sum(n > 2048 for n in lens) >= 3
    assert min(b - a for a, b in zip(lens, lens[1:])) >= 4
    with pytest.raises(ValueError):
        arch.lane_lengths(100, 4, 2048)


def test_picks_margin_allows_a_near_tie_and_nothing_else(arch):
    import numpy as np

    scores = np.array([[0.9, 0.8, 0.5, 0.499, 0.1]])
    assert arch.picks_margin(np.array([[0, 1, 2]]), scores) == 0.0
    assert arch.picks_margin(np.array([[1, 3, 0]]), scores) == pytest.approx(0.001)
    assert arch.picks_margin(np.array([[0, 1, 4]]), scores) == pytest.approx(0.4)


@pytest.mark.parametrize("variant", ["no_window", "no_gate"])
def test_a_wrong_reference_is_not_agreed_with(arch, tiny, variant):
    model, params = tiny
    out = arch.compare_served(model, params, seed=2**31 + 3, prompt_len=640,
                              decode_steps=3, variant=variant)
    assert not out["ok"] and out["ratio"] > arch.TOLERANCE, out


def test_the_configuration_is_rehearsed_end_to_end(tmp_path):
    """The cell's configuration under a tiny mix in a copy (the cell's own
    mix holds 3328-token prompts, 30 burst buckets on a CPU): served through
    the engine by the module's family, compared by its ``compare_served``,
    and the program's counters reach the new metrics."""
    bench, man = _copy_of_the_benchmark(tmp_path)
    (bench / "traffic" / "tiny.json").write_text(json.dumps(TINY_MIX))
    man["workloads"].append({"name": "trinity-mini.tiny", "config": "trinity-mini",
                             "traffic": "tiny", "chips": 1, "why": "test"})
    for m in man["per_layer"]:
        if m["name"] in NEW_METRICS:
            m["workloads"].append("trinity-mini.tiny")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(man))
    out, line = _rehearse(tmp_path, "trinity-mini.tiny", "2")
    assert line["correct"] and line["failed"] == 0 and line["attempted"] > 0
    assert "'picks_margin'" in out and "'tolerance': 0.1" in out
    got = line["metrics"]
    assert {"tpot_p50_ms", "setup_s", "decode_step_device_ms",
            "decode_hbm_roofline", "scheduler_host_share", "prefill_device_share",
            "load_s", "warm_s"} <= set(got)
    # 2 picks a live lane in each of 4 routed layers, of 8 experts
    assert 0.0 < got["moe_experts_touched_share"]["value"] <= 100.0
    assert 1.0 <= got["moe_rows_per_touched_expert"]["value"] <= 4.0
    # contexts of at most 56 under a window of 256: whole blocks of 128
    assert got["kv_window_read_share"]["value"] >= 100.0
    # the kernel runs on a TPU only: its reader finds nothing here
    assert "moe_expert_hbm_roofline" not in got
    run_dir, = (bench / "_runs" / "trinity-mini.tiny").glob("*-trace2-0")
    served = json.load(open(run_dir / "model" / "jax_config.json"))
    assert served["family"] == "benchmark_afmoe"
    assert served["config"]["block"] == "afmoe"
    counters = json.load(open(run_dir / "capture.json"))["counters"]
    assert counters["moe_layer_steps"] > 0 and counters["kv_positions_live_window"] > 0
