"""The jamba architecture's benchmark files hold what the others' hold: the
manifest finds them, the configuration states every published key and that
nothing of the model is cut, the costs are the file's own arithmetic at the
published sizes, each new reader reads a fixture and falls silent without
its counter or its kernel, the served model agrees with the plain reference
at a tiny size and each wrong one does not, and the tiny CPU rehearsal runs
the configuration end to end. CPU only.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_jamba.py -q
"""

from __future__ import annotations

import gzip
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

CONFIG = "jamba2-3b"
CELL = CONFIG + ".thinking"
NEW_METRICS = ("ssm_state_hbm_roofline", "ssm_prefill_hbm_roofline",
               "ssm_step_bytes_share", "ssm_prefill_steps_walked_share")
JOINED = ("decode_attn_hbm_roofline", "compiles_in_window", "warm_compile_s",
          "warm_trace_lower_s", "warm_cache_miss_share")
REDUCED = ["max_position_embeddings"]
# the catalog row's ``config`` (model-configs/architectures.jsonl,
# AI21-Jamba2-3B), as the file must hold it but for REDUCED
CATALOG = {
    "attn_layer_offset": 7, "attn_layer_period": 14, "expert_layer_offset": 1,
    "expert_layer_period": 2, "hidden_act": "silu", "hidden_size": 2560,
    "intermediate_size": 8192, "mamba_conv_bias": True, "mamba_d_conv": 4,
    "mamba_d_state": 16, "mamba_dt_rank": 160, "mamba_expand": 2,
    "mamba_proj_bias": False, "max_position_embeddings": 262144,
    "model_type": "jamba", "num_attention_heads": 20, "num_experts": 1,
    "num_experts_per_tok": 1, "num_hidden_layers": 28,
    "num_key_value_heads": 1, "num_logits_to_keep": 1, "rms_norm_eps": 1e-06,
    "sliding_window": None, "tie_word_embeddings": True,
    "use_mamba_kernels": True, "vocab_size": 65536}


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
        CONFIG, "thinking", 1)
    assert len(cell["why"]) <= 200
    assert arch.__name__ == "benchmark.architectures.jamba"
    assert all(hasattr(arch, name) for name in manifest.ARCHITECTURE_API)
    assert all(hasattr(arch, name) for name in (
        "ssm_state_bytes", "ssm_step_bytes", "ssm_prefill_bytes",
        "decode_attn_bytes"))
    got = {m["name"] for m in manifest.metrics_of(man, "per_layer", CELL)}
    assert set(NEW_METRICS) | set(JOINED) <= got
    # the six that every cell reports
    assert {"decode_step_device_ms", "decode_hbm_roofline", "scheduler_host_share",
            "prefill_device_share", "load_s", "warm_s"} <= got
    e2e = {m["name"] for m in manifest.metrics_of(man, "end_to_end", CELL)}
    assert {"tpot_p50_ms", "setup_s"} <= e2e <= {
        "tpot_p50_ms", "setup_s", "tokens_per_s"}
    # the metrics that move tokens_per_s come with it or not at all
    moved = {m["moves"] for m in manifest.metrics_of(man, "per_layer", CELL)}
    assert ("tokens_per_s" in moved) == ("tokens_per_s" in e2e)
    for name in NEW_METRICS:
        entry = next(m for m in man["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [CELL] and entry["moves"] == "tpot_p50_ms"
        assert entry["unit"] == "%"
        assert callable(manifest.layer_reader(ROOT, man, name))
    for name in JOINED:
        entry = next(m for m in man["per_layer"] if m["name"] == name)
        assert entry["workloads"][-1] == CELL
    assert man["workloads"][-1] == cell and man["configs"][-1]["name"] == CONFIG
    assert len(json.dumps(man)) < 64 << 10


def test_the_configuration_states_every_key_and_cuts_nothing(man, cfg, arch):
    entry = next(c for c in man["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == cfg["reduced"] == REDUCED
    assert entry["source"] == cfg["source"] and len(entry["why"]) <= 200
    assert set(cfg["reduced_why"]) == set(REDUCED)
    for key in ("assumed", "deployment", "memory_arithmetic", "weights",
                "server", "server_why"):
        assert cfg[key], key
    # every key of the catalog row, unchanged but for the one in reduced
    assert {k: cfg[k] for k in CATALOG if k not in REDUCED} == {
        k: v for k, v in CATALOG.items() if k not in REDUCED}
    assert cfg["max_position_embeddings"] == 8192
    assert cfg["max_position_embeddings_published"] == 262144
    assert cfg["server"] in ({"slots": 192, "max_seq": 8192},
                             {"slots": 128, "max_seq": 8192})
    assert "one whole replica" in cfg["deployment"]
    # 28 layers, 26 Mamba and attention at 7 and 21
    assert arch.n_kinds(cfg) == (26, 2)
    kw = arch.model_kwargs(cfg, 2**31 + 5)
    assert kw["seed"] < 2**31 and kw["block"] == "jamba"
    assert (kw["n_layers"], kw["max_seq"], kw["vocab_size"], kw["d_model"],
            kw["d_ff"]) == (28, 8192, 65536, 2560, 8192)
    assert (kw["n_heads"], kw["n_kv_heads"], kw["head_dim"]) == (20, 1, 128)
    assert (kw["attn_layer_period"], kw["attn_layer_offset"],
            kw["mamba_d_state"], kw["mamba_d_conv"], kw["mamba_dt_rank"],
            kw["mamba_expand"], kw["norm_eps"]) == (14, 7, 16, 4, 160, 2, 1e-6)
    assert set(arch.rehearsal(cfg)) <= set(cfg)
    assert {"written_from", "layer_order", "layer", "mamba_in_proj",
            "mamba_conv", "mamba_x_proj", "mamba_dt", "mamba_scan",
            "mamba_cache", "state_dtype", "state_layout", "attention",
            "head_dim", "torch_dtype"} <= set(cfg["assumed"])
    assert {"residual_scale", "matrices", "qk", "conv", "A_log_and_D", "b_dt",
            "small_norms", "norms"} <= set(cfg["weights"])
    for key, value in (("num_experts", 16), ("mamba_conv_bias", False),
                       ("mamba_proj_bias", True), ("tie_word_embeddings", False),
                       ("sliding_window", 4096)):
        with pytest.raises(manifest.ManifestError):
            arch.model_kwargs(dict(cfg, **{key: value}), 0)


def test_thinking_is_the_traffic_the_issue_named(man, cfg):
    mix = manifest.traffic(ROOT, man, "thinking")
    assert traffic.n_clients(mix, cfg["server"]["slots"]) == cfg["server"]["slots"] + 8
    assert (mix["loop"], mix["ramp_s"], mix["drain_s"], mix["temperature"]) == (
        "closed", 30, 0, 0.0)
    assert mix["classes"] == [[90, 557, 2], [250, 811, 2], [450, 1093, 2],
                              [900, 1381, 2]]
    cycle = traffic.cycle(mix)
    assert sum(p for _k, p, _n in cycle) / len(cycle) == 422.5
    assert sum(n for _k, _p, n in cycle) / len(cycle) == 960.5
    ends = sorted({p + n for _k, p, n in cycle})
    assert ends == [647, 1061, 1543, 2281] and ends[-1] <= cfg["server"]["max_seq"]
    assert all(p % 128 for p in traffic.prompt_lens(mix))
    # the four output lengths are primes
    assert all(all(n % d for d in range(2, int(n ** 0.5) + 1))
               for _k, _p, n in cycle)


def test_costs_against_hand_arithmetic_at_the_published_sizes(cfg, arch):
    assert arch.mamba_params(cfg) == 41_241_792
    assert arch.attention_params(cfg) == 13_762_560
    assert arch.ffn_params(cfg) == 62_914_560
    assert arch.total_params(cfg) == 3_029_337_472
    assert arch.ssm_state_bytes(cfg) == 327_680 + 30_720
    assert arch.ssm_kernel_state_bytes(cfg) == 327_680
    assert arch.kv_bytes_per_position_and_layer(cfg) == 512
    # without the program's counters: nothing, not a guess
    assert arch.decode_step_bytes(cfg, 1000, {}) is None
    assert arch.ssm_step_bytes(cfg, {"ssm_lane_steps": 5}) is None
    assert arch.ssm_step_share(cfg, {}) is None
    assert arch.ssm_prefill_bytes(cfg, {}) is None
    assert arch.decode_attn_bytes(cfg, {}) is None
    steps, lanes, at = 50, 192, 1050
    counters = {"ssm_layer_steps": 26 * steps,
                "ssm_lane_steps": 26 * lanes * steps,
                "kv_rows_live": 2 * steps * lanes * at,
                "kv_rows_read": 2 * steps * lanes * 1280,
                "ssm_prefill_steps_walked": 26 * 1690,
                "ssm_prefill_steps_bucket": 26 * 2176}
    # ISSUE 55's step: 6.06 GB of weights, 3.58 GB of state and tails, 0.2
    # GB of keys and values
    assert arch.ssm_step_bytes(cfg, counters) == 192 * 26 * 358_400 * 2
    assert arch.ssm_step_bytes(cfg, counters) == pytest.approx(3.58e9, rel=0.01)
    none_live = arch.decode_step_bytes(cfg, 0, counters)
    assert none_live == 2 * 3_029_337_472 + 192 * 26 * 358_400 * 2
    assert arch.decode_step_bytes(cfg, 1000, counters) - none_live == 1000 * 1024
    mine, step = arch.ssm_step_share(cfg, counters)
    assert step == arch.decode_step_bytes(cfg, lanes * at, counters)
    assert step == pytest.approx(9.84e9, rel=0.01)
    assert mine / step == pytest.approx(0.364, abs=0.005)
    assert arch.decode_attn_bytes(cfg, counters) == counters["kv_rows_read"] * 512
    assert arch.ssm_prefill_bytes(cfg, counters) == 26 * 1690 * (
        3 * 5120 + 2 * 16) * 2
    one = arch.prefill_flops(cfg, 1024, 1, {})
    assert arch.prefill_flops(cfg, 2 * 1024, 2, {}) == pytest.approx(2 * one)
    assert arch.prefill_flops(cfg, 0, 0, {}) == 0.0
    assert arch.prefill_attention_flops(cfg, 1024, 1) == pytest.approx(
        4 * 20 * 128 * 2 * 1024 * 1024 / 2)
    # the matrix products of every layer: 5.72 GFLOP a token outside the
    # head (2 x 2.86 G parameters), the scan's element-wise work not in it
    per_token = (one - arch.prefill_attention_flops(cfg, 1024, 1)
                 - 2.0 * 2560 * 65536) / 1024
    assert per_token == pytest.approx(2 * (3_029_337_472 - 167_772_160), rel=0.001)


def _run(cfg, arch, counters, device_ops=(), modules=None):
    return {"config": cfg, "architecture": arch,
            "cell": {"name": "no-such-cell"},
            "peaks": {"hbm_bytes_per_s": 819e9},
            "trace": {"device_ops": [list(op) for op in device_ops],
                      "modules": modules or {}},
            "trace_counters": ({}, {"program": {"counters": counters}})}


def test_each_new_reader_on_a_fixture_and_without_its_counters(man, cfg, arch):
    read = {name: manifest.layer_reader(ROOT, man, name)
            for name in NEW_METRICS + JOINED[:1]}
    steps = 280
    counters = {"ssm_layer_steps": 26 * steps,
                "ssm_lane_steps": 26 * 190 * steps,
                "kv_rows_live": 2 * steps * 190 * 1050,
                "kv_rows_read": 2 * steps * 190 * 1200,
                "ssm_prefill_steps_walked": 26 * 169_000,
                "ssm_prefill_steps_bucket": 26 * 217_600}
    ops = [("jit_fused_burst:selective_scan_step_f32_192_26_16_5120", 1.4),
           ("jit_fused_burst:ragged_decode_attention_bf16_192_1_20_128", 0.09),
           ("jit_fused_burst:fusion_kOutput_bf16_192_10240", 0.4),
           ("jit_prefill_many:selective_scan_prefill_bf16_8_512_5120", 0.5),
           ("jit_prefill_one:selective_scan_prefill_bf16_1_1024_5120", 0.25),
           ("jit_prefill_many:fusion_kOutput_bf16_8_512_10240", 0.3)]
    run = _run(cfg, arch, counters, ops)
    # 190 lanes x 26 layers x 327,680 B in and out a step over 1.4 s
    assert read["ssm_state_hbm_roofline"](run) == pytest.approx(
        100 * 26 * 190 * steps * 2 * 327_680 / 819e9 / 1.4)
    assert 60 < read["ssm_state_hbm_roofline"](run) < 100
    # the scan's operands for the steps walked over both prefill executables
    assert read["ssm_prefill_hbm_roofline"](run) == pytest.approx(
        100 * 26 * 169_000 * (3 * 5120 + 32) * 2 / 819e9 / 0.75)
    assert 0 < read["ssm_prefill_hbm_roofline"](run) < 100
    assert read["ssm_step_bytes_share"](run) == pytest.approx(36.2, abs=1.0)
    assert read["ssm_prefill_steps_walked_share"](run) == pytest.approx(
        100 * 169_000 / 217_600)
    assert read["decode_attn_hbm_roofline"](run) == pytest.approx(
        100 * counters["kv_rows_read"] * 512 / 819e9 / 0.09)
    # a program without the counters (the parent), or a trace without the
    # kernel: nothing, and no error
    for empty in (_run(cfg, arch, {}), _run(cfg, arch, {"tokens": 5}),
                  {**run, "trace_counters": None}):
        assert all(read[name](empty) is None for name in NEW_METRICS)
    assert read["ssm_state_hbm_roofline"](_run(cfg, arch, counters)) is None
    assert read["ssm_prefill_hbm_roofline"](
        _run(cfg, arch, counters, ops[:3])) is None
    # another architecture's module has no such arithmetic: silent
    other = manifest.architecture(ROOT, man, "decoder")
    assert all(read[name](_run(cfg, other, counters, ops)) is None
               for name in ("ssm_state_hbm_roofline", "ssm_prefill_hbm_roofline",
                            "ssm_step_bytes_share"))


def test_the_kernels_seconds_come_from_the_runs_events_where_it_left_them(
        cfg, arch, tmp_path, monkeypatch):
    """The reduction names ten ops; the prefill scan runs at a shape a
    (rows, bucket), so its seconds are summed from the run's own events, by
    the reduction's labels and inside the named executables alone."""
    op = ("%selective_scan_prefill.{n} = (bf16[{b},{t},5120]{{2,1,0}}, "
          "f32[{b},16,5120]{{2,1,0}}) custom-call(%a), "
          "custom_call_target=\"tpu_custom_call\"")
    events = {"devices": [{"name": "/device:TPU:0", "modules": [
        ["jit_prefill_many(7)", 0.0, 1.0], ["jit_prefill_one(8)", 2.0, 1.0],
        ["jit_fused_burst(9)", 4.0, 1.0]], "ops": [
        [op.format(n=1, b=8, t=512), 0.1, 0.25, ""],
        [op.format(n=2, b=4, t=128), 0.5, 0.125, ""],
        [op.format(n=1, b=1, t=1024), 2.5, 0.0625, ""],
        # not inside a prefill executable: another program's
        [op.format(n=1, b=1, t=1024), 4.5, 0.5, ""],
        ["%fusion.3 = bf16[8,512,10240]{2,1,0} fusion(%x), kind=kOutput",
         0.7, 0.2, ""]]}], "host": []}
    here = tmp_path / "benchmark"
    run_dir = here / "_runs" / "a-cell" / "seed1-trace2-0"
    run_dir.mkdir(parents=True)
    with gzip.open(run_dir / "trace_events.json.gz", "wt") as f:
        json.dump(events, f)
    monkeypatch.setattr(arch, "__file__", str(here / "architectures" / "jamba.py"))
    run = dict(_run(cfg, arch, {}), cell={"name": "a-cell"})
    assert arch.kernel_seconds(
        run, ("jit_prefill_one", "jit_prefill_many"),
        "selective_scan_prefill") == pytest.approx(0.4375)
    assert arch.kernel_seconds(run, ("jit_fused_burst",),
                               "selective_scan_prefill") == pytest.approx(0.5)
    assert arch.kernel_seconds(run, ("jit_fused_burst",),
                               "selective_scan_step") is None
    # no events of this cell: the ten named ops
    other = dict(run, cell={"name": "b-cell"}, trace={"device_ops": [
        ["jit_fused_burst:selective_scan_step_f32_192_26_16_5120", 0.75]]})
    assert arch.kernel_seconds(other, ("jit_fused_burst",),
                               "selective_scan_step") == 0.75


def test_the_decode_kernels_share_does_not_fall_silent_below_the_ten_ops(
        man, cfg, arch, tmp_path, monkeypatch):
    """On the recorded chip trace (``trace_fixture.json.gz``: two bursts of
    a dense cell), with the ragged decode kernel's events put into its
    bursts: where the kernel is among the ten ops the reduction names, the
    share from the run's events is the ten ops' own; made faster, under
    the tenth op, it is the eleventh, the ten name it no more, and the
    share still reads, higher by what the kernel gained."""
    import copy

    from benchmark import trace
    from benchmark.tests.test_benchmark import FIXTURE

    read = manifest.layer_reader(ROOT, man, "decode_attn_hbm_roofline")
    with gzip.open(FIXTURE, "rt") as f:
        recorded = json.load(f)["events"]
    tenth = trace.reduce(recorded)["device_ops"][-1][1]
    bursts = [m for m in recorded["devices"][0]["modules"]
              if trace.executable_of(m[0]) == "jit_fused_burst"]
    kernel = ("%ragged_decode_attention.7 = bf16[192,1,20,128]{3,2,1,0} "
              "custom-call(%q, %k, %v), custom_call_target=\"tpu_custom_call\"")
    counters = {"kv_rows_read": 2 * 16 * 190 * 1200}
    need = arch.decode_attn_bytes(cfg, counters)
    here = tmp_path / "benchmark"
    monkeypatch.setattr(arch, "__file__", str(here / "architectures" / "jamba.py"))

    def run_with(seconds, cell):
        """The recorded events with the kernel taking ``seconds`` in all,
        as a run of ``cell`` leaves them and reduces them."""
        events = copy.deepcopy(recorded)
        events["devices"][0]["ops"] += [
            [kernel, start + 1e-4, seconds / len(bursts), ""]
            for _name, start, _d in bursts]
        run_dir = here / "_runs" / cell / "seed1-trace2-0"
        run_dir.mkdir(parents=True)
        with gzip.open(run_dir / "trace_events.json.gz", "wt") as f:
            json.dump(events, f)
        return dict(_run(cfg, arch, counters), cell={"name": cell},
                    trace=trace.reduce(events))

    def share(seconds):
        return 100.0 * need / 819e9 / seconds

    label = "jit_fused_burst:ragged_decode_attention_bf16_192_1_20_128"
    among = run_with(3 * tenth, "among-the-ten")
    assert label in dict(among["trace"]["device_ops"])
    assert read(among) == pytest.approx(share(3 * tenth), rel=1e-9)
    eleventh = run_with(0.5 * tenth, "the-eleventh")
    assert label not in dict(eleventh["trace"]["device_ops"])
    assert len(eleventh["trace"]["device_ops"]) == trace.TOP
    assert read(eleventh) == pytest.approx(share(0.5 * tenth), rel=1e-9)
    assert read(eleventh) == pytest.approx(6 * read(among))
    # no such kernel in the burst, or no counter: silent either way
    assert read(dict(eleventh, cell={"name": "no-such-cell"})) is None
    assert read(dict(eleventh, trace_counters=({}, {"program": {
        "counters": {}}}))) is None


@pytest.fixture(scope="module")
def tiny(cfg, arch):
    small = dict(cfg, **arch.rehearsal(cfg), name="tiny")
    kw = arch.model_kwargs(small, 7)
    seed = kw.pop("seed")
    model = arch.SeededJambaLM(**kw)
    return model, model.init_params(seed)


@pytest.fixture
def tiny_batcher(tiny):
    """The comparison builds no batcher of its own: a test passes one, of
    the tiny cell's size (32 lanes, a cache of 1024 positions)."""
    from seldon_core_tpu.serving.continuous import ContinuousBatcher

    model, params = tiny
    batcher = ContinuousBatcher(model, params, slots=32, max_seq=1024,
                                steps_per_poll=4)
    yield batcher
    batcher.close()


def test_the_seeded_draw_is_the_programs_own_stacked_by_run(arch, tiny):
    import jax
    import numpy as np

    from seldon_core_tpu.models.jamba import JambaLM

    model, params = tiny
    assert type(model).__mro__[1] is JambaLM
    assert all(a.dtype == jax.numpy.bfloat16
               for a in jax.tree_util.tree_leaves(params))
    own = JambaLM.init_params(model, 7)
    assert jax.tree_util.tree_structure(own) == jax.tree_util.tree_structure(params)
    assert [r["w_in"].shape[0] for r in params["runs"]] == [1, 3]
    # the same draw, to a rounding of the served dtype (inside one compiled
    # program the draw's scale fuses into the cast)
    for mine, theirs in zip(jax.tree_util.tree_leaves(params),
                            jax.tree_util.tree_leaves(own)):
        np.testing.assert_allclose(np.asarray(mine, np.float32),
                                   np.asarray(theirs), rtol=1e-2, atol=1e-6)


def test_the_served_model_agrees_with_the_reference_at_a_tiny_size(
        arch, tiny, tiny_batcher):
    model, params = tiny
    # as the engine's batcher is warmed for its traffic: prompts of 100 and
    # 300, the longest context ending at 516
    tiny_batcher._warm_args = {"prompt_lens": (100, 300),
                               "max_new_tokens": 216, "batch_sizes": (1, 4, 8)}
    out = arch.compare_served(model, params, seed=2**31 + 3,
                              batcher=tiny_batcher)
    assert out["ok"] and out["ratio"] < arch.TOLERANCE, out
    # every lane from the batcher's own prefills in the buckets the traffic
    # pads to, 128 and 512; the traffic's own lengths, the shortest (under
    # the convolution's 4 taps) and the longest among the shown lanes
    assert out["prompt_len"] == 512 and {b for b, _m in out["prefill_calls"]} == {
        128, 512}
    assert sum(m for _b, m in out["prefill_calls"]) == 28
    assert set(out["rows_ratio_lanes"]) == {"3", "100", "300", "512"}
    assert set(out["state_ratio_insert"]) == {"3", "100", "300", "512"}
    assert out["state_ratio"] <= arch.STATE_TOLERANCE
    assert out["slow_state_ratio"] <= arch.SLOW_STATE_TOLERANCE
    assert out["slow_state_lanes"] == 1      # the longest lane, at 512
    assert out["tails_ratio"] <= arch.TAILS_TOLERANCE
    assert out["rows_ratio"] <= arch.ROWS_TOLERANCE
    assert out["prefill_margin"] <= arch.TOLERANCE
    assert (out["lanes"], out["lanes_live"], out["positions"],
            out["decode_steps"]) == (32, 28, 113, 4)
    assert (out["cache_len"], out["bucket"], out["borrowed"]) == (1024, 512, False)
    assert out["counters_are_the_lengths"] and out["burst_counters_hold"]
    assert out["prefill_counters_hold"]
    assert out["idle_untouched"] and out["inserted"]


def test_the_comparisons_lanes_are_the_cells(man, cfg, arch):
    """The comparison's batch is the configuration's under the cell's
    traffic: most lanes live, every eighth idle, lengths spread to where
    the mix's longest contexts end, the mix's own prompt lengths among
    them, lanes on both sides of the kernel's block edge, a prompt shorter
    than the convolution and prompts that fill their buckets."""
    lanes = cfg["server"]["slots"]
    mix = manifest.traffic(ROOT, man, "thinking")
    asked = tuple(sorted(set(traffic.prompt_lens(mix))))
    end = max(asked) + traffic.max_new(mix)
    assert (asked, end) == ((90, 250, 450, 900), 2281)
    start = arch.lane_lengths(lanes, end - 8, asked)
    lens = sorted(start.values())
    assert len(start) == lanes - lanes // 8 == len(set(lens))
    assert set(range(lanes)) - set(start) == set(range(5, lanes, 8))
    assert lens[0] == 3 and lens[-1] + 8 == end and set(asked) < set(lens)
    assert {255, 256, 257, 128, 512, 1024} <= set(lens)
    # lanes in every bucket the mix's prompts pad to, and past the last
    for lo, hi in ((0, 128), (128, 512), (512, 1024), (1024, end)):
        assert sum(lo < n <= hi for n in lens) >= 3
    with pytest.raises(ValueError):
        arch.lane_lengths(lanes, 100)


@pytest.mark.parametrize("variant", [
    "weights_8bit", "state_bf16", "no_dt_norm", "no_conv_bias", "no_D",
    "A_positive", "rotary"])
def test_a_wrong_reference_is_not_agreed_with(arch, tiny, tiny_batcher,
                                              variant):
    model, params = tiny
    # a state rounded after every token shows in the channels that forget
    # slowest, once a lane holds a memory's worth of positions: 1000 here
    out = arch.compare_served(
        model, params, seed=2**31 + 3, variant=variant, batcher=tiny_batcher,
        prompt_len=1000 if variant == "state_bf16" else 512)
    assert not out["ok"], out
    assert (not out["finite"] or out["ratio"] > arch.TOLERANCE
            or out["state_ratio"] > arch.STATE_TOLERANCE
            or out["slow_state_ratio"] > arch.SLOW_STATE_TOLERANCE
            or out["rows_ratio"] > arch.ROWS_TOLERANCE
            or out["tails_ratio"] > arch.TAILS_TOLERANCE), out
    with pytest.raises(ValueError):
        arch.compare_served(model, params, seed=1, prompt_len=512,
                            variant="no_such_model", batcher=tiny_batcher)


def test_a_burst_that_leaves_a_live_lane_out_is_not_agreed_with(
        arch, tiny, tiny_batcher):
    """The burst's own control: its tokens, its rows, its tails, its states
    and its counters are held to the step's, and the reference alone would
    not see it."""
    model, params = tiny
    out = arch.compare_served(model, params, seed=2**31 + 3, prompt_len=512,
                              variant="burst_idles_a_lane",
                              batcher=tiny_batcher)
    assert not out["ok"], out
    assert out["ratio"] <= arch.TOLERANCE and out["state_ratio"] <= arch.STATE_TOLERANCE
    assert out["burst_states_ratio"] > arch.BURST_TOLERANCE
    assert not out["burst_counters_hold"]


def test_the_comparison_borrows_the_serving_batchers_cache_and_hands_it_back(
        arch, tiny):
    """On the chip a second cache of the cell's size is not built: the
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
    batcher = ContinuousBatcher(model, params, slots=16, max_seq=1024,
                                steps_per_poll=4)
    try:
        assert arch._serving_batcher(params) is batcher
        out = arch.compare_served(model, params, seed=11)
        assert out["ok"] and out["borrowed"], out
        assert (out["lanes"], out["cache_len"], out["prompt_len"]) == (16, 1024, 512)
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
        if m["name"] in NEW_METRICS + JOINED[:1]:
            m["workloads"].append(CONFIG + ".tiny")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(man))
    out, line = _rehearse(tmp_path, CONFIG + ".tiny", "2")
    assert line["correct"] and line["failed"] == 0 and line["attempted"] > 0
    assert "'state_ratio'" in out and "'tails_ratio'" in out
    assert "'borrowed': True" in out
    got = line["metrics"]
    assert {"tpot_p50_ms", "setup_s", "decode_step_device_ms",
            "decode_hbm_roofline", "scheduler_host_share", "prefill_device_share",
            "load_s", "warm_s"} <= set(got)
    assert 0.0 < got["ssm_step_bytes_share"]["value"] < 100.0
    assert 0.0 < got["ssm_prefill_steps_walked_share"]["value"] < 100.0
    # the kernels run on a TPU only: their readers find nothing here
    assert "ssm_state_hbm_roofline" not in got
    assert "ssm_prefill_hbm_roofline" not in got
    assert "decode_attn_hbm_roofline" not in got
    run_dir, = (bench / "_runs" / (CONFIG + ".tiny")).glob("*-trace2-0")
    served = json.load(open(run_dir / "model" / "jax_config.json"))
    assert served["family"] == "benchmark_jamba"
    assert served["config"]["block"] == "jamba"
    counters = json.load(open(run_dir / "capture.json"))["counters"]
    assert counters["ssm_lane_steps"] > 0
    assert counters["ssm_layer_steps"] % 4 == 0       # 4 Mamba layers of 6
    assert counters["ssm_lane_steps"] <= 4 * counters["ssm_layer_steps"]
    assert 0 < counters["kv_rows_live"] <= counters["kv_rows_read"]
    assert 0 < counters["ssm_prefill_steps_walked"] < counters[
        "ssm_prefill_steps_bucket"]
