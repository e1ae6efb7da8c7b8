"""The evabyte architecture's benchmark files hold what the others' hold:
the manifest finds them, the configuration states every published key and
its cut, the costs are the file's own arithmetic, each new reader reads a
fixture and falls silent without its counters or its kernel, the served
model agrees with the plain reference at a tiny size and each wrong one
does not, and the tiny CPU rehearsal runs the configuration end to end.
CPU only.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_evabyte.py -q
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

CONFIG = "evabyte"
CELL = CONFIG + ".bytebatch"
NEW_METRICS = ("eva_cache_hbm_roofline", "eva_rows_read_share",
               "eva_context_read_share", "eva_cache_bytes_share",
               "eva_prefill_windows_walked_share")
# the lists of ``tokens_per_s`` and of the metrics that move it: the cell is
# on none of them (its ``tokens_per_s`` spreads over half the 1% bound from
# seed to seed: PERF.md section 7)
NOT_JOINED = ("tokens_per_s", "lane_occupancy", "device_idle_share.batch",
              "admit_turn_max_ms", "read_wait_max_ms",
              "dispatch_found_drained_share")
REDUCED = ["num_hidden_layers", "max_position_embeddings", "max_seq_length"]
# the catalog row's ``config`` (model-configs/architectures.jsonl, EvaByte),
# as the file must hold it but for REDUCED
CATALOG = {
    "attention_bias": False, "attention_class": "eva", "chunk_size": 16,
    "fp32_ln": False, "fp32_logits": True, "fp32_skip_add": True,
    "hidden_act": "silu", "hidden_size": 4096, "init_cutoff_factor": None,
    "init_fn": "v2", "init_std": 0.01275, "intermediate_size": 11008,
    "lazy_init": True, "max_position_embeddings": 32768,
    "max_seq_length": 32768, "mixedp_attn": True, "model_type": "evabyte",
    "norm_add_unit_offset": True, "num_attention_heads": 32,
    "num_chunks": None, "num_hidden_layers": 32, "num_key_value_heads": 32,
    "num_pred_heads": 8, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 100000, "tie_word_embeddings": False, "vocab_size": 320,
    "window_size": 2048}


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
        CONFIG, "bytebatch", 1)
    assert len(cell["why"]) <= 200
    assert arch.__name__ == "benchmark.architectures.evabyte"
    assert all(hasattr(arch, name) for name in manifest.ARCHITECTURE_API)
    got = {m["name"] for m in manifest.metrics_of(man, "per_layer", CELL)}
    assert set(NEW_METRICS) <= got and not set(NOT_JOINED) & got
    assert {"decode_step_device_ms", "decode_hbm_roofline", "scheduler_host_share",
            "prefill_device_share", "load_s", "warm_s"} <= got
    assert {m["name"] for m in manifest.metrics_of(man, "end_to_end", CELL)} == {
        "tpot_p50_ms", "setup_s"}
    # it joins no other family's mechanism
    assert not [n for n in got if n.startswith(("moe_", "mla_", "gdn_",
                                                "kv_window_"))]
    assert "device_idle_share.latency" not in got
    for name in NEW_METRICS:
        entry = next(m for m in man["per_layer"] if m["name"] == name)
        assert CELL in entry["workloads"] and entry["unit"] == "%"
        assert entry["moves"] == "tpot_p50_ms"
        assert callable(manifest.layer_reader(ROOT, man, name))
    for name in NOT_JOINED:
        entry = next(m for m in man["per_layer"] + man["end_to_end"]
                     if m["name"] == name)
        assert CELL not in entry["workloads"]
    entry = next(c for c in man["configs"] if c["name"] == CONFIG)
    assert len(entry["why"]) <= 200 and entry["file"].endswith("evabyte.json")
    assert len(json.dumps(man)) < 64 << 10
    assert arch.served_slots() == cfg["server"]["slots"]


def test_the_configuration_states_every_key_and_its_cut(man, cfg, arch):
    entry = next(c for c in man["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == cfg["reduced"] == REDUCED
    assert entry["source"] == cfg["source"] == (
        "https://huggingface.co/EvaByte/EvaByte/blob/main/config.json")
    assert set(cfg["reduced_why"]) == set(REDUCED)
    for key in ("assumed", "deployment", "memory_arithmetic", "weights",
                "server", "server_why"):
        assert cfg[key], key
    # every key of the catalog row, unchanged but for the three in reduced
    assert {k: cfg[k] for k in CATALOG if k not in REDUCED} == {
        k: v for k, v in CATALOG.items() if k not in REDUCED}
    assert (cfg["num_hidden_layers"], cfg["max_position_embeddings"],
            cfg["max_seq_length"]) == (8, 16384, 16384)
    assert cfg["num_hidden_layers_published"] == CATALOG["num_hidden_layers"]
    assert cfg["max_position_embeddings_published"] == 32768
    assert cfg["served_layers"] == list(range(8))
    # no width, head count, vocabulary, head, window or chunk is cut
    assert not set(REDUCED) & {"hidden_size", "intermediate_size", "vocab_size",
                               "num_pred_heads", "window_size", "chunk_size"}
    assert cfg["server"]["max_seq"] == 16384 and cfg["server"]["slots"] in (16, 20, 24)
    for said in ("v5e-4", "four pipeline stages of 8 layers", "stage 0",
                 "no layer shared"):
        assert said in cfg["deployment"], said
    # every choice ISSUE 44's Tentpole 1 lists
    assert {"pooling_logits_scaled", "no_chunk_size_bias", "rotary",
            "mixedp_attn", "head", "eva_attention", "cache"} <= set(cfg["assumed"])
    assert "head-major" in cfg["assumed"]["head"]
    assert "BEFORE pooling" in cfg["assumed"]["rotary"]
    kw = arch.model_kwargs(cfg, 2**31 + 5)
    assert kw["seed"] < 2**31 and kw["block"] == "evabyte"
    assert (kw["n_layers"], kw["max_seq"], kw["vocab_size"], kw["d_model"],
            kw["d_ff"], kw["n_heads"], kw["n_kv_heads"]) == (
        8, 16384, 320, 4096, 11008, 32, 32)
    assert (kw["window_size"], kw["chunk_size"], kw["num_pred_heads"],
            kw["norm_add_unit_offset"], kw["fp32_skip_add"], kw["rope_theta"],
            kw["norm_eps"]) == (2048, 16, 8, True, True, 1e5, 1e-5)
    assert set(arch.rehearsal(cfg)) <= set(cfg)
    for key, value in (("attention_class", "softmax"), ("num_chunks", 4),
                       ("rope_scaling", {"type": "yarn"}), ("fp32_logits", False),
                       ("num_key_value_heads", 8), ("served_layers", [0, 1]),
                       ("max_seq_length", 32768), ("tie_word_embeddings", True)):
        with pytest.raises(manifest.ManifestError):
            arch.model_kwargs(dict(cfg, **{key: value}), 0)


def test_bytebatch_gives_the_configurations_lanes_their_clients(man, cfg):
    mix = manifest.traffic(ROOT, man, "bytebatch")
    slots = cfg["server"]["slots"]
    assert traffic.n_clients(mix, slots) == slots + 8
    # the traffic ISSUE 44 named, its ramp too
    assert (mix["loop"], mix["ramp_s"], mix["drain_s"], mix["temperature"]) == (
        "closed", 20, 0, 0.0)
    assert mix["classes"] == [[1000, 1536, 2], [3000, 768, 2],
                              [6100, 2048, 2], [12200, 1024, 2]]
    cycle = traffic.cycle(mix)
    assert sum(p for _k, p, _n in cycle) / len(cycle) == 5575
    assert sum(n for _k, _p, n in cycle) / len(cycle) == 1344
    ends = sorted({p + n for _k, p, n in cycle})
    assert ends == [2536, 3768, 8148, 13224] and ends[-1] <= cfg["server"]["max_seq"]
    # prompt lengths are multiples of neither a chunk nor a window
    assert all(p % 16 and p % 2048 for p in traffic.prompt_lens(mix))


def test_costs_against_the_configs_own_arithmetic(cfg, arch):
    assert arch.eva_row_bytes(cfg) == 16_384 == 2 * 32 * 128 * 2
    assert arch.layer_params(cfg) == pytest.approx(202.39e6, rel=1e-4)
    # weights once: 8 x 404.8 MB, the norm and the 21 MB head
    assert arch.step_weight_bytes(cfg) == pytest.approx(3.259e9, rel=2e-3)
    # a lane at position 6,124: 1,014 ring rows and 319... whole windows'
    # summaries: 2 x 128
    assert arch.rows_at(cfg, 6124) == (6123 % 2048 + 1) + 2 * 128
    assert arch.rows_at(cfg, 1) == 1 and arch.rows_at(cfg, 2048) == 2048
    assert arch.rows_at(cfg, 2049) == 1 + 128
    # without the program's counters: from the live positions
    assert arch.eva_step_rows(cfg, {}) is None
    assert arch.eva_step_rows(cfg, {"steps": 10}) is None
    assert arch.decode_step_bytes(cfg, 0, {}) == arch.step_weight_bytes(cfg)
    assert arch.decode_step_bytes(cfg, 2049, {}) - arch.step_weight_bytes(cfg) == (
        8 * 129 * 16_384)
    # ISSUE 44's step: 20 lanes x 1,333 rows x 8 layers x 16,384 B = 3.49 GB
    # beside the weights: 6.75 GB, the cache 52%
    steps = 50
    counters = {"steps": steps, "eva_lane_steps": 20 * 8 * steps,
                "eva_window_rows_live": 20 * 8 * steps * 1014,
                "eva_summary_rows_live": 20 * 8 * steps * 319,
                "eva_positions_live": 20 * 8 * steps * 6124}
    assert arch.eva_step_rows(cfg, counters) == 20 * 1333
    step = arch.decode_step_bytes(cfg, 123456, counters)    # counters first
    assert step == pytest.approx(6.75e9, rel=0.01)
    assert (step - arch.step_weight_bytes(cfg)) / step == pytest.approx(0.52, abs=0.01)
    one = arch.prefill_flops(cfg, 2048, 1, {})
    assert arch.prefill_flops(cfg, 2 * 2048, 2, {}) == pytest.approx(2 * one)
    assert arch.prefill_flops(cfg, 0, 0, {}) == 0.0
    # the local causal half of each window and the remote rectangle
    assert arch.prefill_attention_flops(cfg, 2048, 1) == pytest.approx(
        4 * 4096 * 8 * 2048 * 2048 / 2)
    assert arch.prefill_attention_flops(cfg, 2 * 2048 + 100, 1) == pytest.approx(
        4 * 4096 * 8 * (2 * 2048 * 2048 / 2 + 100 * 100 / 2
                        + 128 * 2048 + 2 * 128 * 100))
    # a walked prompt is computed at its own windows, not the bucket's:
    # 3,000 bytes in the 16,384 bucket walk 2 of 8
    walked = arch.prefill_flops(cfg, 16384, 1, {
        "eva_prefill_windows_walked": 2, "eva_prefill_windows_bucket": 8})
    assert walked == pytest.approx(arch.prefill_flops(cfg, 4096, 1, {}))
    # ISSUE 44: a request's prefill (mean 5,575 bytes) is ~19 TFLOP
    assert 2.9e9 < one / 2048 < 3.6e9


def _run(cfg, arch, counters, device_ops=()):
    return {"config": cfg, "architecture": arch,
            "peaks": {"hbm_bytes_per_s": 819e9},
            "trace": {"device_ops": [list(op) for op in device_ops]},
            "trace_counters": ({}, {"program": {"counters": counters}})}


def test_each_new_reader_on_a_fixture_and_without_its_counters(man, cfg, arch):
    read = {name: manifest.layer_reader(ROOT, man, name) for name in NEW_METRICS}
    steps = 400
    per = 20 * 8 * steps
    counters = {"steps": steps, "eva_lane_steps": per,
                "eva_window_rows_live": per * 1014,
                "eva_summary_rows_live": per * 319,
                "eva_rows_read": per * (1024 + 384),
                "eva_positions_live": per * 6124,
                "eva_summaries_written": per // 16,
                "eva_prefill_windows_walked": 12,
                "eva_prefill_windows_bucket": 25}
    ops = [("jit_fused_burst:eva_decode_attention_bf16_20_32_1_128", 2.0),
           ("jit_fused_burst:fusion_kCustom_bf16_20_32_1024_128", 0.3),
           ("jit_prefill_one:eva_prefill_attention_bf16_32_2048_128", 0.1)]
    run = _run(cfg, arch, counters, ops)
    assert read["eva_rows_read_share"](run) == pytest.approx(
        100 * 1408 / 1333, abs=0.01)
    assert read["eva_context_read_share"](run) == pytest.approx(
        100 * 1333 / 6124, abs=0.01)
    assert read["eva_cache_bytes_share"](run) == pytest.approx(51.7, abs=0.5)
    assert read["eva_prefill_windows_walked_share"](run) == pytest.approx(48.0)
    # 20 x 8 x 400 x 1,333 rows x 16,384 B at 819 GB/s over 2 s
    assert read["eva_cache_hbm_roofline"](run) == pytest.approx(
        100 * per * 1333 * 16_384 / 819e9 / 2.0)
    assert 60 < read["eva_cache_hbm_roofline"](run) < 100
    # a program without the counters (the parent), or a trace without the
    # kernel: nothing, and no error
    for empty in (_run(cfg, arch, {}), _run(cfg, arch, {"tokens": 5, "steps": 9}),
                  {**run, "trace_counters": None}):
        assert all(read[name](empty) is None for name in NEW_METRICS)
    assert read["eva_cache_hbm_roofline"](_run(cfg, arch, counters)) is None


@pytest.fixture(scope="module")
def tiny(cfg, arch):
    small = dict(cfg, **arch.rehearsal(cfg), name="tiny")
    kw = arch.model_kwargs(small, 7)
    seed = kw.pop("seed")
    model = arch.SeededEvaByteLM(**kw)
    return model, model.init_params(seed)


@pytest.fixture
def tiny_batcher(tiny):
    """The comparison builds no batcher of its own: a test passes one (16
    lanes, a cache of eight of the tiny windows)."""
    from seldon_core_tpu.serving.continuous import ContinuousBatcher

    model, params = tiny
    batcher = ContinuousBatcher(model, params, slots=16, max_seq=512)
    yield batcher
    batcher.close()


def test_the_served_model_agrees_with_the_reference_at_a_tiny_size(
        arch, tiny, tiny_batcher):
    import jax

    model, params = tiny
    assert type(model).__mro__[1].__name__ == "EvaByteLM"
    assert all(a.dtype == jax.numpy.bfloat16
               for a in jax.tree_util.tree_leaves(params))
    out = arch.compare_served(model, params, seed=2**31 + 3, decode_steps=4,
                              batcher=tiny_batcher)
    assert out["ok"] and out["ratio"] < arch.TOLERANCE, out
    assert out["rows_ratio"] <= arch.ROWS_TOLERANCE
    assert len(out["ratio_by_head"]) == 3     # every head is compared
    # 14 of 16 lanes live, 4 steps each
    assert (out["lanes"], out["lanes_live"], out["positions"]) == (16, 14, 56)
    assert (out["cache_len"], out["borrowed"]) == (512, False)
    assert out["counters_are_the_lengths"] and out["burst_counters_hold"]
    assert out["walked_right"] and out["idle_untouched"] and out["inserted"]
    assert out["burst_tokens_agree"] > 0.9
    # each lane's prefill walked its own windows of 64
    assert all(walked == -(-n // 64) for n, (walked, _bucket) in zip(
        [n for _lane, n in sorted(arch.lane_lengths(16, 64, 8, 512, 4).items())],
        out["windows_walked"]))


def test_the_comparisons_lanes_are_the_cells(cfg, arch):
    """The comparison's batch is the configuration's: every eighth lane
    idle, each live lane at a length of its own, from shorter than a chunk
    to the cache's last window, the edges ISSUE 44 names among them."""
    slots = cfg["server"]["slots"]
    start = arch.lane_lengths(slots, 2048, 16, 16384, 8)
    lens = sorted(start.values())
    assert set(range(slots)) - set(start) == set(range(5, slots, 8))
    assert len(set(lens)) == len(lens)
    assert lens[0] < 16 and lens[-1] > 14336 and lens[-1] + 8 < 16384
    assert 2046 in lens                          # t = 2046 .. crosses the edge
    assert any(n % 2048 in (1, 2, 3, 4) and n > 2048 for n in lens)
    assert any(n % 2048 > 2048 - 16 for n in lens)         # a full ring
    assert any(0 < n < 2048 and n > 16 for n in lens)      # no summary visible
    assert {n % 16 for n in lens} >= {0, 1, 15}
    assert {3000, 12200} <= set(lens)            # the cell's walked prompts


@pytest.fixture(scope="module")
def tiny_served(arch, tiny):
    """One serving for every wrong reference (``judge(serve(...))``: the
    served half does not depend on the variant)."""
    from seldon_core_tpu.serving.continuous import ContinuousBatcher

    model, params = tiny
    batcher = ContinuousBatcher(model, params, slots=16, max_seq=512)
    try:
        return arch.serve(model, params, 2**31 + 3, 4, batcher=batcher)
    finally:
        batcher.close()


@pytest.mark.parametrize("variant", [
    "weights_8bit", "pool_mean", "mu_phi_swapped",
    "summaries_of_current_window", "two_softmaxes", "sliding_window",
    "summaries_8bit", "rope_theta_1e4", "norm_w_only"])
def test_a_wrong_reference_is_not_agreed_with(arch, tiny, tiny_served, variant):
    model, params = tiny
    out = arch.judge(model, tiny_served, params, variant)
    assert not out["ok"], out
    assert (out["ratio"] > arch.TOLERANCE
            or out["rows_ratio"] > arch.ROWS_TOLERANCE), out
    # what the served half alone decides still holds
    assert out["burst_counters_hold"] and out["counters_are_the_lengths"]


def test_an_unknown_variant_is_refused(arch, tiny, tiny_served):
    model, params = tiny
    with pytest.raises(ValueError, match="unknown variant"):
        arch.judge(model, tiny_served, params, "no_such_model")


def test_a_burst_that_leaves_a_live_lane_out_is_not_agreed_with(
        arch, tiny, tiny_batcher):
    """The burst's own control: its tokens, its rows and its counters are
    held to the step's, and the reference alone would not see it."""
    model, params = tiny
    out = arch.compare_served(model, params, seed=2**31 + 3, decode_steps=4,
                              variant="burst_idles_a_lane", batcher=tiny_batcher)
    assert not out["ok"], out
    assert out["ratio"] <= arch.TOLERANCE and out["rows_ratio"] <= arch.ROWS_TOLERANCE
    assert not out["burst_counters_hold"]


def test_the_comparison_borrows_the_serving_batchers_cache_and_hands_it_back(
        arch, tiny):
    import gc

    import numpy as np

    from seldon_core_tpu.serving.continuous import ContinuousBatcher

    model, params = tiny
    gc.collect()    # the batchers of the tests before this one
    with pytest.raises(ValueError, match="none was given"):
        arch.compare_served(model, params, seed=11)
    batcher = ContinuousBatcher(model, params, slots=8, max_seq=512)
    try:
        assert arch._serving_batcher(params) is batcher
        out = arch.compare_served(model, params, seed=11)
        assert out["ok"] and out["borrowed"], out
        # the burst compared is the batcher's own: its steps a poll
        assert out["positions"] == out["lanes_live"] * batcher._k
        assert (out["lanes"], out["cache_len"]) == (8, 512)
        assert batcher._cache is not None
        batcher.start()
        prompt = np.random.default_rng(0).integers(0, 320, size=150).tolist()
        first = list(batcher.submit(prompt, max_new_tokens=5).result(timeout=300))
        again = list(batcher.submit(prompt, max_new_tokens=5).result(timeout=300))
        assert first == again and len(first) == 155
    finally:
        batcher.close()


def test_the_configuration_is_rehearsed_end_to_end(tmp_path):
    """The cell's configuration under a tiny mix in a copy: served through
    the engine by the module's family, compared by its ``compare_served``
    on the engine's own cache, and the program's counters reach the new
    metrics. The tiny mix's second class (40 + 16 bytes) crosses chunk
    edges; its prompts stay inside the rehearsal's window of 64."""
    bench, man = _copy_of_the_benchmark(tmp_path)
    mix = dict(TINY_MIX, classes=[[20, 8, 1], [40, 16, 1], [150, 24, 1]])
    (bench / "traffic" / "tiny.json").write_text(json.dumps(mix))
    man["workloads"].append({"name": CONFIG + ".tiny", "config": CONFIG,
                             "traffic": "tiny", "chips": 1, "why": "test"})
    for m in man["per_layer"]:
        if m["name"] in NEW_METRICS:
            m["workloads"].append(CONFIG + ".tiny")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(man))
    out, line = _rehearse(tmp_path, CONFIG + ".tiny", "2")
    assert line["correct"] and line["failed"] == 0 and line["attempted"] > 0
    assert "'rows_ratio'" in out and "'borrowed': True" in out
    got = line["metrics"]
    assert {"tpot_p50_ms", "setup_s", "decode_step_device_ms",
            "decode_hbm_roofline", "scheduler_host_share", "prefill_device_share",
            "load_s", "warm_s"} <= set(got)
    # each kind rounds up to its block of 128 rows
    assert got["eva_rows_read_share"]["value"] >= 100.0
    assert 0.0 < got["eva_context_read_share"]["value"] <= 100.0
    assert 0.0 < got["eva_cache_bytes_share"]["value"] < 100.0
    assert 0.0 < got["eva_prefill_windows_walked_share"]["value"] <= 100.0
    # the kernel runs on a TPU only: its reader finds nothing here
    assert "eva_cache_hbm_roofline" not in got
    run_dir, = (bench / "_runs" / (CONFIG + ".tiny")).glob("*-trace2-0")
    served = json.load(open(run_dir / "model" / "jax_config.json"))
    assert served["family"] == "benchmark_evabyte"
    assert served["config"]["block"] == "evabyte"
    counters = json.load(open(run_dir / "capture.json"))["counters"]
    assert counters["eva_lane_steps"] > 0
    assert counters["eva_positions_live"] >= (
        counters["eva_window_rows_live"] + counters["eva_summary_rows_live"])
    assert counters["eva_rows_read"] >= (
        counters["eva_window_rows_live"] + counters["eva_summary_rows_live"])
    assert counters["eva_prefill_windows_bucket"] >= (
        counters["eva_prefill_windows_walked"]) > 0
