"""The qwen3_next architecture's benchmark files hold what the others' hold:
the manifest finds them, the configuration states every published width and
its cut, the costs are the file's own arithmetic, each new reader reads a
fixture and falls silent without its counter or its kernel, the served
model agrees with the plain reference at a tiny size and each wrong one
does not, and the tiny CPU rehearsal runs the configuration end to end.
CPU only.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_qwen3_next.py -q
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

CONFIG = "qwen3-next-80b-a3b"
CELL = CONFIG + ".longbatch"
NEW_METRICS = ("gdn_state_hbm_roofline", "moe_held_rows_share",
               "moe_held_rows_per_touched_expert")
JOINED = ("moe_experts_touched_share", "moe_expert_hbm_roofline")
REDUCED = ["num_hidden_layers", "num_experts", "vocab_size",
           "max_position_embeddings"]
# the catalog row's ``config`` (model-configs/architectures.jsonl,
# Qwen3-Next-80B-A3B-Instruct), as the file must hold it but for REDUCED
CATALOG = {
    "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5120,
    "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
    "linear_num_key_heads": 16, "linear_num_value_heads": 32,
    "linear_value_head_dim": 128, "max_position_embeddings": 262144,
    "mlp_only_layers": [], "model_type": "qwen3_next",
    "moe_intermediate_size": 512, "norm_topk_prob": True,
    "num_attention_heads": 16, "num_experts": 512, "num_experts_per_tok": 10,
    "num_hidden_layers": 48, "num_key_value_heads": 2,
    "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06, "rope_scaling": None,
    "rope_theta": 10000000, "shared_expert_intermediate_size": 512,
    "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 151936}


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
        CONFIG, "longbatch", 1)
    assert len(cell["why"]) <= 200
    assert arch.__name__ == "benchmark.architectures.qwen3_next"
    got = {m["name"] for m in manifest.metrics_of(man, "per_layer", CELL)}
    assert set(NEW_METRICS) | set(JOINED) <= got
    assert {"decode_step_device_ms", "decode_hbm_roofline", "scheduler_host_share",
            "prefill_device_share", "load_s", "warm_s"} <= got
    # a closed loop with every lane busy: tokens_per_s and the two layer
    # metrics that move it, as ISSUE 38 names them
    assert {m["name"] for m in manifest.metrics_of(man, "end_to_end", CELL)} == {
        "tpot_p50_ms", "tokens_per_s", "setup_s"}
    assert {"lane_occupancy", "device_idle_share.batch"} <= got
    assert "device_idle_share.latency" not in got
    # all routed pairs over the HELD experts touched is four times the rows
    # an expert here is read for: the cell reads its own metric of that
    assert "moe_rows_per_touched_expert" not in got
    for name in NEW_METRICS:
        entry = next(m for m in man["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [CELL] and entry["moves"] == "tpot_p50_ms"
        assert callable(manifest.layer_reader(ROOT, man, name))
    # the cell is the last entry of every list it joined
    for name in JOINED + ("tokens_per_s", "lane_occupancy",
                          "device_idle_share.batch"):
        entry = next(m for m in man["per_layer"] + man["end_to_end"]
                     if m["name"] == name)
        assert entry["workloads"][-1] == CELL
    assert man["workloads"][-1] is cell and man["configs"][-1]["name"] == CONFIG


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
    assert (cfg["num_hidden_layers"], cfg["num_experts"], cfg["vocab_size"],
            cfg["max_position_embeddings"]) == (8, 128, 37984, 4096)
    # the published counts and the share are stated beside the cut ones
    assert cfg["num_experts_published"] == CATALOG["num_experts"]
    assert cfg["vocab_size_published"] == CATALOG["vocab_size"] == 4 * cfg["vocab_size"]
    assert cfg["experts_held"] == [0, 128] and arch.held(cfg) == (0, 128)
    assert cfg["state_dtype"] == "float32"
    assert "four chips" in cfg["deployment"] and "four pipeline stages" in cfg["deployment"]
    # the floors: two whole periods, at least 8 experts, an eighth of the vocabulary
    assert arch.served_layer_types(cfg) == (
        ["linear_attention"] * 3 + ["full_attention"]) * 2
    kw = arch.model_kwargs(cfg, 2**31 + 5)
    assert kw["seed"] < 2**31 and kw["block"] == "qwen3_next"
    assert (kw["n_routed_experts"], kw["experts_held"], kw["experts_per_tok"]) == (
        512, [0, 128], 10)
    assert (kw["n_layers"], kw["max_seq"], kw["vocab_size"]) == (8, 4096, 37984)
    assert (kw["n_heads"], kw["n_kv_heads"], kw["head_dim"]) == (16, 2, 256)
    assert set(arch.rehearsal(cfg)) <= set(cfg)
    assert {"zero_centred_norms", "gated_attention", "partial_rotary",
            "gated_delta_net", "moe", "mtp_not_served"} <= set(cfg["assumed"])
    bad = dict(cfg, experts_held=[0, 64])
    with pytest.raises(manifest.ManifestError):
        arch.held(bad)


def test_longbatch_gives_the_configurations_lanes_their_clients(man, cfg):
    mix = manifest.traffic(ROOT, man, "longbatch")
    assert traffic.n_clients(mix, cfg["server"]["slots"]) == (
        cfg["server"]["slots"] + 8)
    assert max(p + new for _k, p, new in traffic.cycle(mix)) <= cfg["server"]["max_seq"]


def test_costs_against_the_configs_own_arithmetic(cfg, arch):
    assert arch.expert_params(cfg) * arch.BYTES == 6_291_456         # 6.29 MB
    assert arch.gdn_state_bytes(cfg) == 2_097_152                    # 2.10 MB
    assert arch.kv_bytes_per_position(cfg) == 2 * 2048               # 2 full layers
    # without the program's counters: nothing, not a guess
    assert arch.decode_step_bytes(cfg, 1000, {}) is None
    assert arch.decode_step_bytes(cfg, 1000, {"moe_layer_steps": 8}) is None
    steps = 50
    counters = {"moe_layer_steps": 8 * steps,
                "moe_experts_touched": 8 * steps * 59.9,
                "gdn_lane_steps": 6 * 32 * steps}
    none_live = arch.decode_step_bytes(cfg, 0, counters)
    experts = 8 * 59.9 * 6_291_456
    state = 6 * 32 * 2 * (2_097_152 + 3 * 8192 * 2)
    # what lies outside: 6 x 76 + 2 x 63 MB of layers and 0.16 GB of head
    assert none_live - experts - state == pytest.approx(0.737e9, rel=0.01)
    assert experts == pytest.approx(3.02e9, rel=0.01)
    assert state == pytest.approx(0.824e9, rel=0.01)
    assert arch.decode_step_bytes(cfg, 1000, counters) - none_live == 1000 * 4096
    one = arch.prefill_flops(cfg, 4096, 1, {})
    assert arch.prefill_flops(cfg, 2 * 4096, 2, {}) == pytest.approx(2 * one)
    assert arch.prefill_flops(cfg, 4096 + 512, 2, {}) < one + arch.prefill_flops(
        cfg, 512, 1, {})
    assert arch.prefill_flops(cfg, 0, 0, {}) == 0.0
    # the experts held here take a quarter of the picks
    per_token = one / 4096
    assert 0.6e9 < per_token < 1.2e9


def _run(cfg, arch, counters, device_ops=()):
    return {"config": cfg, "architecture": arch,
            "peaks": {"hbm_bytes_per_s": 819e9},
            "trace": {"device_ops": [list(op) for op in device_ops]},
            "trace_counters": ({}, {"program": {"counters": counters}})}


def test_each_new_reader_on_a_fixture_and_without_its_counters(man, cfg, arch):
    read = {name: manifest.layer_reader(ROOT, man, name)
            for name in NEW_METRICS + JOINED}
    counters = {"moe_layer_steps": 8000, "moe_experts_touched": 479_000,
                "moe_rows_routed": 2_560_000, "moe_rows_held": 640_000,
                "gdn_lane_steps": 192_000}
    ops = [("jit_fused_burst:gated_delta_step_f32_32_32_128_128", 1.25),
           ("jit_fused_burst:touched_experts_ffn_f32_32_2048", 4.5),
           ("jit_prefill_many:x", 9.0)]
    run = _run(cfg, arch, counters, ops)
    assert read["moe_held_rows_share"](run) == pytest.approx(25.0)
    # 192,000 lane-steps x 2.10 MB x 2 at 819 GB/s is 0.983 s of the kernel's 1.25
    assert read["gdn_state_hbm_roofline"](run) == pytest.approx(
        100 * 192_000 * 2 * 2_097_152 / 819e9 / 1.25)
    assert read["gdn_state_hbm_roofline"](run) < 100
    # the readers the cell joined size an expert and count the held from the file
    assert read["moe_experts_touched_share"](run) == pytest.approx(
        100 * 479_000 / 8000 / 128)
    # the rows an expert here was read for: the held pairs, not all routed
    assert read["moe_held_rows_per_touched_expert"](run) == pytest.approx(640 / 479)
    assert read["moe_expert_hbm_roofline"](run) == pytest.approx(
        100 * 479_000 * 6_291_456 / 819e9 / 4.5)
    # a program without the counters (the parent), or a trace without the
    # kernel: nothing, and no error
    for empty in (_run(cfg, arch, {}), _run(cfg, arch, {"tokens": 5}),
                  {**run, "trace_counters": None}):
        assert all(read[name](empty) is None for name in NEW_METRICS)
    assert read["gdn_state_hbm_roofline"](_run(cfg, arch, counters)) is None


@pytest.fixture(scope="module")
def tiny(cfg, arch):
    small = dict(cfg, **arch.rehearsal(cfg), name="tiny")
    kw = arch.model_kwargs(small, 7)
    seed = kw.pop("seed")
    model = arch.SeededQwen3NextLM(**kw)
    return model, model.init_params(seed)


def test_the_served_model_agrees_with_the_reference_at_a_tiny_size(arch, tiny):
    import jax

    model, params = tiny
    assert type(model).__mro__[1].__name__ == "Qwen3NextLM"
    assert all(a.dtype == jax.numpy.bfloat16
               for a in jax.tree_util.tree_leaves(params))
    out = arch.compare_served(model, params, seed=2**31 + 3, prompt_len=640,
                              decode_steps=3, lanes=32)
    assert out["ok"] and out["ratio"] < arch.TOLERANCE, out
    assert out["picks_margin"] <= arch.PICKS_MARGIN and out["picks_agree"] > 0.9
    assert out["state_ratio"] <= arch.STATE_TOLERANCE
    # the burst's batch, most lanes live: 28 lanes x 3 steps and the prefill's last
    assert (out["lanes"], out["lanes_live"], out["positions"]) == (32, 28, 85)
    assert out["counters_are_the_picks"]
    # 28 lanes x 4 picks of 16 experts, 4 held: all touched, a quarter lands
    assert out["experts_touched_a_layer_step"] == 4
    assert 0.15 < out["held_rows_share"] < 0.35


def test_the_comparisons_lanes_are_the_cells(cfg, arch):
    """The comparison's batch is the configuration's: most lanes live, every
    eighth idle, one goes on where the whole prompt ended, three sit at a
    chunk's edge, and no two step at one position."""
    assert arch.served_slots() == cfg["server"]["slots"]
    start = arch.lane_lengths(32, 2304, 4)
    lens = sorted(start.values())
    assert len(start) == 28 and set(range(32)) - set(start) == {5, 13, 21, 29}
    assert lens[0] == 144 and lens[-1] == 2304
    assert {n % 64 for n in lens} >= {0, 1, 63}
    assert sum(n < 2304 for n in lens) == 27       # below their bucket
    assert min(b - a for a, b in zip(lens, lens[1:])) >= 4
    with pytest.raises(ValueError):
        arch.lane_lengths(32, 100, 4)


@pytest.mark.parametrize("variant", ["no_decay", "rotary_all", "weights_8bit"])
def test_a_wrong_reference_is_not_agreed_with(arch, tiny, variant):
    model, params = tiny
    out = arch.compare_served(model, params, seed=2**31 + 3, prompt_len=640,
                              decode_steps=3, variant=variant, lanes=32)
    assert not out["ok"], out
    assert (out["ratio"] > arch.TOLERANCE or out["picks_margin"] > arch.PICKS_MARGIN
            or out["state_ratio"] > arch.STATE_TOLERANCE), out


def test_a_burst_that_leaves_a_live_lane_out_is_not_agreed_with(arch, tiny):
    """The burst's own control: its tokens, its cache and its counters
    are held to the step's, and the reference alone would not see it."""
    model, params = tiny
    out = arch.compare_served(model, params, seed=2**31 + 3, prompt_len=640,
                              decode_steps=3, variant="burst_idles_a_lane",
                              lanes=32)
    assert not out["ok"], out
    assert out["ratio"] <= arch.TOLERANCE and out["state_ratio"] <= arch.STATE_TOLERANCE
    assert out["burst_margin"] > arch.TOLERANCE
    assert max(out["burst_cache_ratio"].values()) > arch.TOLERANCE
    assert not out["burst_counters_hold"]


def test_a_state_row_in_the_wrong_lane_is_not_agreed_with(arch, tiny, monkeypatch):
    """The comparison fills its lanes through the batcher's own insert: a
    state row that lands one lane on is seen (the logits, the state, and
    the idle lane it fell into)."""
    from jax import lax

    real = lax.dynamic_update_slice

    def one_lane_on(operand, update, start):
        if (operand.dtype == "float32" and operand.ndim == 4
                and operand.shape[0] == 32 and update.shape[0] == 1):
            start = (start[0] + 1,) + tuple(start[1:])
        return real(operand, update, start)

    monkeypatch.setattr(lax, "dynamic_update_slice", one_lane_on)
    model, params = tiny
    out = arch.compare_served(model, params, seed=2**31 + 3, prompt_len=640,
                              decode_steps=3, lanes=32)
    assert not out["ok"] and not out["idle_untouched"], out
    assert out["ratio"] > arch.TOLERANCE and out["state_ratio"] > arch.STATE_TOLERANCE


def test_the_configuration_is_rehearsed_end_to_end(tmp_path):
    """The cell's configuration under a tiny mix in a copy: served through
    the engine by the module's family, compared by its ``compare_served``,
    and the program's counters reach the new metrics."""
    bench, man = _copy_of_the_benchmark(tmp_path)
    (bench / "traffic" / "tiny.json").write_text(json.dumps(TINY_MIX))
    man["workloads"].append({"name": CONFIG + ".tiny", "config": CONFIG,
                             "traffic": "tiny", "chips": 1, "why": "test"})
    for m in man["per_layer"]:
        if m["name"] in NEW_METRICS + JOINED:
            m["workloads"].append(CONFIG + ".tiny")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(man))
    out, line = _rehearse(tmp_path, CONFIG + ".tiny", "2")
    assert line["correct"] and line["failed"] == 0 and line["attempted"] > 0
    assert "'state_ratio'" in out and "'picks_margin'" in out
    got = line["metrics"]
    assert {"tpot_p50_ms", "setup_s", "decode_step_device_ms",
            "decode_hbm_roofline", "scheduler_host_share", "prefill_device_share",
            "load_s", "warm_s"} <= set(got)
    # 4 of 16 experts held, 4 picks a live lane
    assert 0.0 < got["moe_experts_touched_share"]["value"] <= 100.0
    assert 5.0 < got["moe_held_rows_share"]["value"] < 60.0
    assert got["moe_held_rows_per_touched_expert"]["value"] >= 1.0
    # the kernels run on a TPU only: their readers find nothing here
    assert "gdn_state_hbm_roofline" not in got
    assert "moe_expert_hbm_roofline" not in got
    run_dir, = (bench / "_runs" / (CONFIG + ".tiny")).glob("*-trace2-0")
    served = json.load(open(run_dir / "model" / "jax_config.json"))
    assert served["family"] == "benchmark_qwen3_next"
    assert served["config"]["block"] == "qwen3_next"
    counters = json.load(open(run_dir / "capture.json"))["counters"]
    assert counters["gdn_lane_steps"] > 0 and counters["moe_rows_held"] > 0
    assert counters["moe_rows_held"] < counters["moe_rows_routed"]
