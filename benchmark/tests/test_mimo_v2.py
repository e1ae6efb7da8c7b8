"""The mimo_v2 architecture's benchmark files hold what the others' hold:
the manifest finds them, the configuration states every published width
and its cut, the costs are the file's own arithmetic, each new reader reads
a fixture and falls silent without its counter or its kernel, the served
model agrees with the plain reference at a tiny size and each wrong one
does not, and the tiny CPU rehearsal runs the configuration end to end.
CPU only.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_mimo_v2.py -q
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

CONFIG = "mimo-v2.5"
CELL = CONFIG + ".longagent"
NEW_METRICS = ("swa_ring_hbm_roofline", "swa_prefill_mxu_roofline")
JOINED = ("moe_expert_hbm_roofline", "moe_experts_touched_share",
          "moe_held_rows_share", "moe_held_rows_per_touched_expert",
          "decode_attn_hbm_roofline", "kv_step_bytes_share",
          "kv_window_read_share", "device_idle_share.latency",
          "compiles_in_window", "warm_compile_s", "warm_trace_lower_s",
          "warm_cache_miss_share")
REDUCED = ["num_hidden_layers", "n_routed_experts", "vocab_size",
           "max_position_embeddings"]
# the catalog row's ``config`` (model-configs/architectures.jsonl,
# MiMo-V2.5), as the file must hold it but for REDUCED
CATALOG = {
    "attention_bias": False, "attention_chunk_size": 128,
    "attention_value_scale": 0.707,
    "attention_projection_layout": "fused_qkv",
    "add_full_attention_sink_bias": False,
    "add_swa_attention_sink_bias": True, "swa_num_key_value_heads": 8,
    "swa_num_attention_heads": 64, "swa_head_dim": 192, "swa_v_head_dim": 128,
    "head_dim": 192, "hidden_act": "silu", "hidden_size": 4096,
    "hybrid_block_size": None,
    "hybrid_layer_pattern": [0] + ([1] * 4 + [0]) + ([1] * 5 + [0]) * 7,
    "intermediate_size": 16384, "layernorm_epsilon": 1e-05,
    "max_position_embeddings": 1048576, "model_type": "mimo_v2",
    "moe_intermediate_size": 2048, "moe_layer_freq": [0] + [1] * 47,
    "n_group": 1, "n_routed_experts": 256, "n_shared_experts": None,
    "norm_topk_prob": True, "num_attention_heads": 64,
    "num_experts_per_tok": 8, "num_hidden_layers": 48,
    "num_key_value_heads": 4, "partial_rotary_factor": 0.334,
    "rope_scaling": {"rope_type": "default", "type": "default"},
    "rope_theta": 10000000, "routed_scaling_factor": None,
    "scoring_func": "sigmoid", "sliding_window": 128,
    "sliding_window_size": 128, "swa_rope_theta": 10000,
    "tie_word_embeddings": False, "topk_group": 1, "topk_method": "noaux_tc",
    "v_head_dim": 128, "vocab_size": 152576}


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
        CONFIG, "longagent", 1)
    assert len(cell["why"]) <= 200
    assert arch.__name__ == "benchmark.architectures.mimo_v2"
    assert all(hasattr(arch, name) for name in manifest.ARCHITECTURE_API)
    got = {m["name"] for m in manifest.metrics_of(man, "per_layer", CELL)}
    assert set(NEW_METRICS) | set(JOINED) <= got
    # the six that every cell reports
    assert {"decode_step_device_ms", "decode_hbm_roofline", "scheduler_host_share",
            "prefill_device_share", "load_s", "warm_s"} <= got
    assert {m["name"] for m in manifest.metrics_of(man, "end_to_end", CELL)} == {
        "tpot_p50_ms", "setup_s"}
    assert not any(m["moves"] == "tokens_per_s"
                   for m in manifest.metrics_of(man, "per_layer", CELL))
    for name in NEW_METRICS:
        entry = next(m for m in man["per_layer"] if m["name"] == name)
        assert entry == {
            "name": name, "unit": "%", "better": "higher",
            "source": "device_trace", "layer": "kernels",
            "moves": "tpot_p50_ms", "workloads": [CELL]}
        assert callable(manifest.layer_reader(ROOT, man, name))
    # appended, nothing moved: the twelfth cell and the tenth configuration
    # (wherever later ones are appended after them), the two new entries
    # side by side, and in each list the cell joined it follows the cells
    # that were there
    cells = [w["name"] for w in man["workloads"]]
    assert cells.index(CELL) == 11 and man["workloads"][11] == cell
    assert man["configs"][9]["name"] == CONFIG
    names = [m["name"] for m in man["per_layer"]]
    at = names.index(NEW_METRICS[0])
    assert names[at:at + 2] == list(NEW_METRICS) and at >= 55
    for name in JOINED:
        entry = next(m for m in man["per_layer"] if m["name"] == name)
        listed = entry["workloads"]
        assert all(cells.index(c) < 11 for c in listed[:listed.index(CELL)])
    assert not any(w["chips"] == 4 for w in man["workloads"][:12])
    assert len(json.dumps(man)) < 64 << 10


def test_the_configuration_states_every_width_and_its_cut(man, cfg, arch):
    entry = next(c for c in man["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == REDUCED == cfg["reduced"]
    assert entry["source"] == cfg["source"] and len(entry["why"]) <= 200
    # every catalog key under its own name, unchanged but the reduced four
    for key, value in CATALOG.items():
        if key not in REDUCED:
            assert cfg[key] == value, key
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"], cfg["max_position_embeddings"]) == (
        7, 16, 19072, 12288)
    assert (cfg["num_hidden_layers_published"],
            cfg["n_routed_experts_published"], cfg["vocab_size_published"],
            cfg["max_position_embeddings_published"]) == tuple(
        CATALOG[k] for k in REDUCED)
    assert set(cfg["reduced_why"]) == set(REDUCED)
    for key in ("assumed", "deployment", "weights", "server",
                "memory_arithmetic", "cache_row"):
        assert cfg[key], key
    # the floors: a whole period and 6 >= 4 layers after the dense one, 16
    # >= 8 experts, an eighth of the vocabulary
    assert cfg["served_layers"] == [0, 1, 2, 3, 4, 5, 6]
    assert arch.served_layer_types(cfg) == [
        "full_attention"] + ["sliding_attention"] * 4 + [
        "full_attention", "sliding_attention"]
    assert arch.n_dense(cfg) == 1 and arch.held(cfg) == (0, 16)
    assert cfg["vocab_size"] * 8 == CATALOG["vocab_size"]
    assert cfg["server"] == {"slots": 64, "max_seq": 12288}
    kw = arch.model_kwargs(cfg, 2**31 + 5)
    assert kw["seed"] < 2**31
    assert (kw["d_model"], kw["n_heads"], kw["n_kv_heads"], kw["swa_n_kv_heads"],
            kw["head_dim"], kw["v_head_width"], kw["rotary_dim"],
            kw["swa_window"]) == (4096, 64, 4, 8, 192, 128, 64, 128)
    assert (kw["rope_theta"], kw["swa_rope_theta"], kw["value_scale"]) == (
        1e7, 1e4, 0.707)
    assert (kw["n_routed_experts"], kw["experts_held"], kw["experts_per_tok"],
            kw["expert_width"], kw["d_ff"], kw["route_scale"]) == (
        256, [0, 16], 8, 2048, 16384, 1.0)
    for key, wrong in (("scoring_func", "softmax"),
                       ("add_full_attention_sink_bias", True),
                       ("swa_head_dim", 128), ("tie_word_embeddings", True)):
        with pytest.raises(manifest.ManifestError):
            arch.model_kwargs(dict(cfg, **{key: wrong}), 1)
    with pytest.raises(manifest.ManifestError):
        arch.held(dict(cfg, experts_held=[0, 8]))


def test_longagent_is_the_traffic_the_issue_named(man, cfg):
    mix = manifest.traffic(ROOT, man, "longagent")
    assert traffic.n_clients(mix, cfg["server"]["slots"]) == 64 + 8
    assert (mix["loop"], mix["drain_s"], mix["temperature"]) == (
        "closed", 0, 0.0)
    assert mix["ramp_s"] in (60, 90)      # the issue's value, or its one fall-back
    assert mix["classes"] == [[1300, 1277, 2], [3100, 1531, 2],
                              [5900, 2039, 2], [9700, 2557, 2]]
    cycle = traffic.cycle(mix)
    assert sum(p for _k, p, _n in cycle) / len(cycle) == 5000
    assert sum(n for _k, _p, n in cycle) / len(cycle) == 1851
    ends = sorted({p + n for _k, p, n in cycle})
    assert ends == [2577, 4631, 7939, 12257] and ends[-1] <= cfg["server"]["max_seq"]
    assert all(p % 128 for p in traffic.prompt_lens(mix))


def test_costs_against_the_configs_own_arithmetic(cfg, arch):
    """The numbers of the file's ``memory_arithmetic`` and of the issue's
    predicted step, from the module's functions."""
    assert arch.expert_params(cfg) == 25_165_824
    assert arch.attention_params(cfg, False) == 89_128_960
    assert arch.attention_params(cfg, True) == 94_371_840 + 64
    # what holds something: 2,560 B a position in a full layer, 5,120 a
    # ring row
    assert arch.kv_bytes_per_position_and_layer(cfg) == 2560
    assert arch.kv_bytes_per_position_and_layer(cfg, True) == 5120
    steps, lanes, ctx = 100, 64, 6800
    counters = {"moe_layer_steps": 6 * steps,
                "moe_experts_touched": int(6 * steps * 13.9),
                "kv_rows_live": lanes * ctx * 2 * steps,
                "kv_rows_read": lanes * 6912 * 2 * steps,
                "kv_positions_read_window": lanes * 128 * 5 * steps,
                "kv_positions_seen_window": lanes * 128 * 5 * steps,
                "kv_positions_live_window": lanes * ctx * 5 * steps}
    step = arch.decode_step_bytes(cfg, lanes * ctx, counters)
    # the issue's prediction: 8.5 GB a step of 64 lanes at 6.8k
    assert 8.3e9 < step < 8.7e9
    mine, whole = arch.kv_step_bytes(cfg, counters)
    assert whole == pytest.approx(step) and mine == lanes * ctx * 2 * 2560
    assert arch.decode_attn_bytes(cfg, counters) == lanes * 6912 * 2 * steps * 2560
    assert arch.ring_bytes(cfg, counters) == lanes * 128 * 5 * steps * 5120
    for f in (arch.decode_attn_bytes, arch.ring_bytes, arch.kv_step_bytes):
        assert f(cfg, {}) is None
    assert arch.decode_step_bytes(cfg, 1.0, {}) is None
    # ~2.2 GFLOP a prompt token at 5k-10k; the band: 128 keys a query
    flops = arch.prefill_flops(cfg, 9728, 1, {})
    assert 2.0e9 < flops / 9728 < 2.6e9
    band = arch.swa_band_flops(cfg, 9728, 1)
    assert band == 2 * 64 * 320 * (9728 * 128 - 128 * 127 / 2) * 5
    assert arch.swa_band_flops(cfg, 0, 0) == 0.0


def _run(cfg, arch, counters, device_ops=(), modules=None, prefills=None):
    run = {"config": cfg, "architecture": arch, "cell": {"name": "no-such-cell"},
           "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
           "trace": {"device_ops": [list(op) for op in device_ops],
                     "modules": modules or {}},
           "trace_counters": ({"stats": {"prefill_tokens": 0, "admitted": 0}},
                              {"stats": prefills or {"prefill_tokens": 0,
                                                      "admitted": 0},
                               "program": {"counters": counters}})}
    return run


def test_each_new_reader_on_a_fixture_and_without_its_counters(man, cfg, arch):
    read = {name: manifest.layer_reader(ROOT, man, name)
            for name in NEW_METRICS + JOINED[:7]}
    steps = 320
    live = 64 * 6800 * 2 * steps
    counters = {"moe_layer_steps": 6 * steps,
                "moe_experts_touched": int(6 * steps * 13.9),
                "moe_rows_routed": 6 * steps * 512, "moe_rows_held": 6 * steps * 32,
                "kv_rows_live": live, "kv_rows_read": int(live * 1.02),
                "kv_positions_read_window": 64 * 128 * 5 * steps,
                "kv_positions_seen_window": 64 * 128 * 5 * steps,
                "kv_positions_live_window": 64 * 6800 * 5 * steps}
    ops = [("jit_fused_burst:touched_experts_ffn_f32_64_4096", 1.9),
           ("jit_fused_burst:ragged_decode_attention_bf16_64_4_16_128", 1.2),
           ("jit_fused_burst:swa_ring_attention_bf16_64_8_8_128", 0.16),
           ("jit_prefill_one:swa_prefill_attention_bf16_64_9728_128", 0.02),
           ("jit_prefill_one:swa_prefill_attention_bf16_64_3584_128", 0.01),
           ("jit_prefill_one:fusion_kOutput_bf16_1_9728_4096", 0.5)]
    modules = {"jit_fused_burst": {"runs": 40, "seconds": 4.5},
               "jit_prefill_one": {"runs": 2, "seconds": 0.6}}
    run = _run(cfg, arch, counters, ops, modules,
               {"prefill_tokens": 9728 + 3584, "admitted": 2})
    assert read["swa_ring_hbm_roofline"](run) == pytest.approx(
        100 * 64 * 128 * 5 * steps * 5120 / 819e9 / 0.16)
    assert 30 < read["swa_ring_hbm_roofline"](run) < 100
    assert read["swa_prefill_mxu_roofline"](run) == pytest.approx(
        100 * arch.swa_band_flops(cfg, 9728 + 3584, 2) / 197e12 / 0.03)
    assert 0 < read["swa_prefill_mxu_roofline"](run) < 100
    assert read["decode_attn_hbm_roofline"](run) == pytest.approx(
        100 * int(live * 1.02) * 2560 / 819e9 / 1.2)
    assert read["kv_window_read_share"](run) == pytest.approx(100 * 128 / 6800)
    assert 20 < read["kv_step_bytes_share"](run) < 35
    assert read["moe_held_rows_share"](run) == pytest.approx(100 / 16)
    assert read["moe_held_rows_per_touched_expert"](run) == pytest.approx(
        32 / 13.9, rel=0.01)
    assert read["moe_experts_touched_share"](run) == pytest.approx(
        100 * 13.9 / 16, rel=0.01)
    assert read["moe_expert_hbm_roofline"](run) == pytest.approx(
        100 * int(6 * steps * 13.9) * 50_331_648 / 819e9 / 1.9)
    # a program without the counters (the parent), or a trace without the
    # kernel: nothing, and no error
    for empty in (_run(cfg, arch, {}), _run(cfg, arch, {"tokens": 5}),
                  {**run, "trace_counters": None}):
        assert all(read[name](empty) is None for name in NEW_METRICS)
    assert read["swa_ring_hbm_roofline"](_run(cfg, arch, counters)) is None
    assert read["swa_prefill_mxu_roofline"](
        _run(cfg, arch, counters, ops[:3], modules,
             {"prefill_tokens": 9728, "admitted": 1})) is None
    # another architecture's module has no such arithmetic: silent
    other = manifest.architecture(ROOT, man, "decoder")
    assert all(read[name](dict(run, architecture=other)) is None
               for name in NEW_METRICS)


@pytest.fixture(scope="module")
def tiny(cfg, arch):
    small = dict(cfg, **arch.rehearsal(cfg), name="tiny")
    kw = arch.model_kwargs(small, 7)
    seed = kw.pop("seed")
    model = arch.SeededMimoV2LM(**kw)
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
    assert type(model).__mro__[1].__name__ == "MimoV2LM"
    assert all(a.dtype == jax.numpy.bfloat16
               for a in jax.tree_util.tree_leaves(params))
    # the live cache's shapes: the window layers' rings are the window long
    # with their own KV heads, the full layers' rows max_seq long with
    # theirs; keys in rows of whole registers beside narrower values
    cache = tiny_batcher._cache
    assert [a.shape for a in cache["k"]] == [(32, 1, 1024, 128)] * 2
    assert [a.shape for a in cache["v"]] == [(32, 1, 1024, 16)] * 2
    assert [a.shape for a in cache["wk"]] == [(32, 2, 16, 128)] * 5
    assert [a.shape for a in cache["wv"]] == [(32, 2, 16, 16)] * 5
    tiny_batcher._warm_args = {"prompt_lens": (100, 300),
                               "max_new_tokens": 216, "batch_sizes": (1, 4, 8)}
    out = arch.compare_served(model, params, seed=2**31 + 3,
                              batcher=tiny_batcher)
    assert out["ok"] and out["ratio"] < arch.TOLERANCE, out
    assert out["prompt_len"] == 512 and {b for b, _m in out["prefill_calls"]} == {
        128, 512}
    assert sum(m for _b, m in out["prefill_calls"]) == 28
    assert set(out["rows_ratio_lanes"]) == {"100", "300", "512"}
    assert max(out["rows_ratio_lanes"].values()) <= arch.ROWS_TOLERANCE
    assert out["prefill_margin"] <= arch.TOLERANCE
    assert out["picks_margin"] <= arch.PICKS_MARGIN and out["picks_agree"] > 0.9
    assert out["rows_ratio"] <= arch.ROWS_TOLERANCE
    assert out["rings_ratio"] <= arch.RINGS_TOLERANCE
    # some lanes' steps crossed a multiple of the window: their rings wrapped
    assert out["lanes_wrapped"] > 0
    assert (out["lanes"], out["lanes_live"], out["positions"],
            out["decode_steps"]) == (32, 28, 113, 4)
    assert (out["cache_len"], out["bucket"], out["borrowed"]) == (1024, 512, False)
    assert out["counters_are_the_picks"] and out["burst_counters_hold"]
    assert out["idle_untouched"] and out["inserted"]
    # 28 lanes x 4 picks of 32 experts, 4 held: about an eighth lands
    assert out["experts_touched_a_layer_step"] > 3
    assert 0.07 < out["held_rows_share"] < 0.2


def _split_keys_family(arch, lose_the_narrow_part: bool = False):
    """The file's tiny family with ANOTHER layout of its cache: a key of
    24 held as a 16-wide part (``k`` / ``wk``) and an 8-wide part (``kr`` /
    ``wkr``: a third array a kind; at the published widths 128 + 64), no
    padding stored. Its step and its prefill are the program's own between
    a join and a split, and it answers the comparison's two questions
    itself: ``cached_rows`` puts the parts together, ``read_block`` is the
    walk's. ``lose_the_narrow_part``: an accessor that hands out zeros for
    the narrow part (a layout that lost it)."""
    import jax
    import jax.numpy as jnp

    from seldon_core_tpu.ops.decode_attention import walk_block

    WIDE = 16
    PARTS = {"k": "kr", "wk": "wkr"}

    class SplitKeys(arch.SeededMimoV2LM):
        def _split(self, tree):
            out = dict(tree)
            for name, rest in PARTS.items():
                if name in tree:
                    out[name] = jax.tree_util.tree_map(
                        lambda a: a[..., :WIDE], tree[name])
                    out[rest] = jax.tree_util.tree_map(
                        lambda a: a[..., WIDE:self.cfg.head_dim], tree[name])
            return out

        def _joined(self, tree):
            # (a tree already whole passes through: the program's own
            # methods call each other with what they were handed)
            out = {n: a for n, a in tree.items() if n not in PARTS.values()}
            for name, rest in PARTS.items():
                if rest in tree:
                    out[name] = jax.tree_util.tree_map(
                        lambda a, b: self._key_rows(
                            jnp.concatenate([a, b], axis=-1)),
                        tree[name], tree[rest])
            return out

        def init_cache(self, batch, max_seq=None):
            return self._split(super().init_cache(batch, max_seq))

        def _forward(self, params, tokens, pad_to, last_index):
            x, slab, picked, counts = super()._forward(
                params, tokens, pad_to, last_index)
            return x, None if slab is None else self._split(slab), picked, counts

        def _step(self, params, cache, *args, **kwargs):
            out, new, counts, picked = super()._step(
                params, self._joined(cache), *args, **kwargs)
            return out, self._split(new), counts, picked

        def position_layers(self, cache):
            return [a for name in cache for a in cache[name]]

        def cache_position_bytes(self, cache):
            return super().cache_position_bytes(self._joined(cache))

        def lane_cache_bytes(self, cache):
            return super().lane_cache_bytes(self._joined(cache))

        def burst_reads_ragged(self, cache, mesh=None):
            return super().burst_reads_ragged(self._joined(cache), mesh)

        # -- what the comparison asks ---------------------------------------

        def cached_rows(self, cache, kind, layer, lanes, positions):
            k, rest, v = (("wk", "wkr", "wv") if kind == "window"
                          else ("k", "kr", "v"))
            at = lanes[:, None]
            wide, narrow, values = (cache[n][layer][at, :, positions]
                                    for n in (k, rest, v))
            if lose_the_narrow_part:
                narrow = jnp.zeros_like(narrow)
            return jnp.concatenate([wide, narrow], axis=-1), values

        def read_block(self, cache_len):
            cfg = self.cfg
            return walk_block(cfg.n_kv_heads, self._key_row,
                              jnp.dtype(cfg.dtype), cache_len, cfg.v_head_width)

    return SplitKeys


def test_the_comparison_reads_the_rows_a_cache_holds_not_its_layout(
        cfg, arch, tiny):
    """A family that lays its keys out otherwise (two parts, a third array
    a kind, no padding) and answers ``cached_rows`` and ``read_block``
    itself is compared on the rows: the same numbers as the plain family's,
    the rings' third array restarted and held in the idle lanes with the
    others; and an accessor that loses the narrow part of every key fails
    the rows' limit and the rings'."""
    from seldon_core_tpu.serving.continuous import ContinuousBatcher

    model, params = tiny
    small = dict(cfg, **arch.rehearsal(cfg), name="tiny")
    kw = arch.model_kwargs(small, 7)
    kw.pop("seed")
    outs = {}
    for name, family in (("plain", type(model)),
                         ("split", _split_keys_family(arch)),
                         ("lost", _split_keys_family(arch, True))):
        other = family(**kw)
        batcher = ContinuousBatcher(other, params, slots=32, max_seq=1024,
                                    steps_per_poll=4)
        try:
            if name != "plain":
                cache = batcher._cache
                assert set(cache) == {"k", "kr", "v", "wk", "wkr", "wv"}
                assert set(arch.window_leaf_names(
                    cache, other.cfg, 32, 1024)) == {"wk", "wkr", "wv"}
                assert [a.shape for a in cache["k"]] == [(32, 1, 1024, 16)] * 2
                assert [a.shape for a in cache["kr"]] == [(32, 1, 1024, 8)] * 2
                assert [a.shape for a in cache["wkr"]] == [(32, 2, 16, 8)] * 5
            outs[name] = arch.compare_served(other, params, seed=2**31 + 3,
                                             prompt_len=512, batcher=batcher)
        finally:
            batcher.close()
    plain, split, lost = outs["plain"], outs["split"], outs["lost"]
    assert plain["ok"] and split["ok"], split
    for key in ("ratio", "picks_margin", "rows_ratio", "rows_ratio_prefill",
                "rows_ratio_steps", "rows_ratio_lanes", "rings_ratio",
                "rings_ratio_insert", "rings_ratio_steps",
                "rings_ratio_prefill", "prefill_margin", "burst_margin",
                "burst_rows_ratio", "burst_rings_ratio", "read_block",
                "lanes_wrapped", "positions", "picks_agree"):
        assert split[key] == plain[key], key
    assert split["idle_untouched"] and split["counters_are_the_picks"]
    assert split["burst_counters_hold"] and split["inserted"]
    # the logits and the picks are the served path's, whatever the accessor
    # says of the cache; the rows and the rings are what it says
    assert not lost["ok"], lost
    assert lost["ratio"] == plain["ratio"]
    assert lost["rows_ratio"] > 10 * arch.ROWS_TOLERANCE
    assert lost["rings_ratio"] > 10 * arch.RINGS_TOLERANCE


def test_a_cache_whose_rings_cannot_be_told_is_refused(arch, tiny):
    """The window layers' leaves are told by what they hold, lanes of
    ``swa_window`` slots and not of the cache's positions, whatever their
    names; a cache that does not divide so raises instead of having the
    wrong arrays restarted and held."""
    model, _params = tiny
    cfg, window = model.cfg, model.cfg.swa_window
    cache = model.init_cache(4, 64)
    assert arch.window_leaf_names(cache, cfg, 4, 64) == ("wk", "wv")
    renamed = {"a": cache["k"], "b": cache["v"], "rings": {
        "keys": cache["wk"], "values": cache["wv"]}}
    assert arch.window_leaf_names(renamed, cfg, 4, 64) == ("rings",)
    for broken in (
            {n: a for n, a in cache.items() if n != "wk"},     # too few for the rows
            dict(cache, wv=[a[:2] for a in cache["wv"]]),      # not led by the lanes
            dict(cache, wk=cache["wk"] + cache["k"][:1]),      # both kinds a key
            dict(cache, scales=[a[..., :1, :1] for a in cache["wk"]]),  # neither
            model.init_cache(4, window)):                      # rings as long as the cache
        with pytest.raises(ValueError):
            arch.window_leaf_names(broken, cfg, 4, len(broken["k"][0][0, 0]))


@pytest.fixture(scope="module")
def served_once(arch, tiny):
    """One serving for every wrong reference: ``serve`` is the program's
    half, ``judge`` the reference's (the controls differ in the second)."""
    from seldon_core_tpu.serving.continuous import ContinuousBatcher

    model, params = tiny
    batcher = ContinuousBatcher(model, params, slots=32, max_seq=1024,
                                steps_per_poll=4)
    try:
        return arch.serve(model, params, 2**31 + 3, prompt_len=512,
                          batcher=batcher)
    finally:
        batcher.close()


@pytest.mark.parametrize("variant", [
    "weights_8bit", "no_sink", "sink_on_full", "one_rope_base", "rotary_all",
    "rotary_interleaved", "window_127", "window_129", "no_value_scale",
    "kv_groups_swapped", "all_bfloat16", "ring_at_bucket_end"])
def test_a_wrong_reference_is_not_agreed_with(arch, tiny, served_once,
                                              variant):
    model, params = tiny
    out = arch.judge(model, served_once, params, variant)
    assert not out["ok"], out
    assert (out["ratio"] > arch.TOLERANCE or out["picks_margin"] > arch.PICKS_MARGIN
            or out["rows_ratio"] > arch.ROWS_TOLERANCE
            or out["rings_ratio"] > arch.RINGS_TOLERANCE), out
    if variant == "ring_at_bucket_end":
        assert out["ratio"] <= arch.TOLERANCE < out["rings_ratio_insert"]


def test_the_sound_reference_is_agreed_with_and_an_unknown_one_refused(
        arch, tiny, served_once):
    model, params = tiny
    assert arch.judge(model, served_once, params)["ok"]
    with pytest.raises(ValueError):
        arch.judge(model, served_once, params, "no_such_model")


def test_a_burst_that_leaves_a_live_lane_out_is_not_agreed_with(
        arch, tiny, tiny_batcher):
    """The burst's own control: its tokens, its rows, its rings and its
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
    on the engine's own cache, and the program's counters reach the
    metrics."""
    bench, man = _copy_of_the_benchmark(tmp_path)
    (bench / "traffic" / "tiny.json").write_text(json.dumps(TINY_MIX))
    man["workloads"].append({"name": CONFIG + ".tiny", "config": CONFIG,
                             "traffic": "tiny", "chips": 1, "why": "test"})
    for m in man["per_layer"]:
        if m["name"] in NEW_METRICS + JOINED[:7]:
            m["workloads"].append(CONFIG + ".tiny")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(man))
    out, line = _rehearse(tmp_path, CONFIG + ".tiny", "2")
    assert line["correct"] and line["failed"] == 0 and line["attempted"] > 0
    assert "'rows_ratio'" in out and "'rings_ratio'" in out
    assert "'borrowed': True" in out
    got = line["metrics"]
    assert {"tpot_p50_ms", "setup_s", "decode_step_device_ms",
            "decode_hbm_roofline", "scheduler_host_share", "prefill_device_share",
            "load_s", "warm_s"} <= set(got)
    # 4 of 32 experts held, 4 picks a live lane
    assert 2.0 < got["moe_held_rows_share"]["value"] < 40.0
    assert got["moe_held_rows_per_touched_expert"]["value"] >= 1.0
    assert 0.0 < got["moe_experts_touched_share"]["value"] <= 100.0
    assert 0.0 < got["kv_step_bytes_share"]["value"] < 100.0
    # a ring of 16 of contexts of tens to hundreds (the dots read every
    # lane's ring whole, idle ones too, so the share can pass the kernel's)
    assert 0.0 < got["kv_window_read_share"]["value"] < 100.0
    # the kernels run on a TPU only: their readers find nothing here
    for name in ("decode_attn_hbm_roofline", "moe_expert_hbm_roofline",
                 "swa_ring_hbm_roofline", "swa_prefill_mxu_roofline"):
        assert name not in got
    run_dir, = (bench / "_runs" / (CONFIG + ".tiny")).glob("*-trace2-0")
    served = json.load(open(run_dir / "model" / "jax_config.json"))
    assert served["family"] == "benchmark_mimo_v2"
    assert served["config"]["block"] == "mimo_v2"
    counters = json.load(open(run_dir / "capture.json"))["counters"]
    assert counters["moe_rows_held"] > 0
    assert 0 < counters["kv_rows_live"] <= counters["kv_rows_read"]
    assert 0 < counters["kv_positions_seen_window"] <= counters[
        "kv_positions_live_window"]
    assert counters["kv_positions_read_window"] > 0
    assert counters["moe_prefill_pairs_routed"] > 0
