"""``moe_prefill_tile_rows_share``: the manifest gives it to the three cells
whose prefills run the grouped experts' kernel, its reader gives the share
on a fixture and nothing where the program has no such counter (the parent
of the PR that added it, a family that names none), and a tiny CPU
rehearsal of ``trinity-mini`` brings the afmoe block's three prefill
counters through the bursts into ``capture.json`` and the metric into the
line. CPU only.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_tile_rows.py -q
"""

from __future__ import annotations

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark import manifest  # noqa: E402
from benchmark.tests.test_benchmark import (  # noqa: E402
    TINY_MIX, _copy_of_the_benchmark, _rehearse)

METRIC = "moe_prefill_tile_rows_share"
CONFIG = "trinity-mini"
CELLS = ["trinity-mini.longbatch", "qwen3-next-80b-a3b.longbatch",
         "joyai-llm-flash.reasoning"]


@pytest.fixture(scope="module")
def man():
    return manifest.load(ROOT)


def _run(counters):
    return {"trace_counters": ({}, {"program": {"counters": counters}})}


def test_the_manifest_gives_the_metric_to_the_three_expert_cells(man):
    entry, = (m for m in man["per_layer"] if m["name"] == METRIC)
    assert entry == {
        "name": METRIC, "unit": "%", "better": "lower",
        "source": "program_counter", "layer": "kernels",
        "moves": "tokens_per_s", "workloads": CELLS}
    for cell in man["workloads"]:
        names = {m["name"] for m in manifest.metrics_of(
            man, "per_layer", cell["name"])}
        assert (METRIC in names) == (cell["name"] in CELLS)
    for name in CELLS:
        assert "tokens_per_s" in {m["name"] for m in manifest.metrics_of(
            man, "end_to_end", name)}


@pytest.mark.parametrize("tiles,routed,share", [
    (32_768, 32_768, 100.0),                     # every row of the bucket, once
    (16_384 + 127 * 128, 32_768, 100 * 32_640 / 32_768),   # 2048 tokens of 4096
    (135 * 128, 1_024, 1687.5),                  # 128 tokens over 128 experts
    (206 * 128, 40_960, 100 * 26_368 / 40_960),  # a share's room of a long prompt
    (0, 4_096, 0.0),                             # nothing landed here
])
def test_the_reader_gives_the_tile_rows_over_the_pairs_routed(man, tiles, routed, share):
    read = manifest.layer_reader(ROOT, man, METRIC)
    assert read(_run({"moe_prefill_tile_rows": tiles,
                      "moe_prefill_pairs_moved": routed,
                      "moe_prefill_pairs_routed": routed})) == pytest.approx(share)


@pytest.mark.parametrize("run", [
    _run({}), _run({"tokens": 5, "moe_rows_routed": 9}),
    _run({"moe_prefill_pairs_moved": 9, "moe_prefill_pairs_routed": 9}),
    _run({"moe_prefill_tile_rows": 0, "moe_prefill_pairs_routed": 0}),
    _run({"moe_prefill_tile_rows": 7}),
    {"trace_counters": None}, {}],
    ids=["no_counters", "other_counters", "the_parents_two",
         "no_prefill_in_the_capture", "half_of_them", "no_capture", "no_run"])
def test_the_reader_finds_nothing_without_the_counter(man, run):
    assert manifest.layer_reader(ROOT, man, METRIC)(run) is None


def test_the_counters_reach_the_capture_and_the_metric_the_line(tmp_path):
    """The configuration under a tiny mix in a copy: 8 experts, top 2, four
    expert layers, prompts of 20 and 40 tokens in buckets of 32 and 64. At
    the rehearsal's widths the shapes are the kernel's, so the tile rows
    are counted as a TPU would work them: tiles of 64 and 128 rows, each
    once for every expert that has a row in it."""
    bench, man = _copy_of_the_benchmark(tmp_path)
    (bench / "traffic" / "tiny.json").write_text(json.dumps(TINY_MIX))
    man["workloads"].append({"name": CONFIG + ".tiny", "config": CONFIG,
                             "traffic": "tiny", "chips": 1, "why": "test"})
    for m in man["per_layer"]:
        if m["name"] == METRIC:
            m["workloads"].append(CONFIG + ".tiny")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(man))
    _out, line = _rehearse(tmp_path, CONFIG + ".tiny", "2")
    assert line["correct"] and line["failed"] == 0 and line["attempted"] > 0
    assert line["metrics"][METRIC]["unit"] == "%"
    assert line["metrics"][METRIC]["value"] > 0.0
    run_dir, = (bench / "_runs" / (CONFIG + ".tiny")).glob("*-trace2-0")
    counters = json.load(open(run_dir / "capture.json"))["counters"]
    # every prefill of a 32- or 64-token bucket routes 2 picks in 4 layers
    # and moves them all; its counters come home a burst after
    # ``prefill_tokens`` counted it
    routed = counters["moe_prefill_pairs_routed"]
    assert routed > 0 and routed % (32 * 2 * 4) == 0
    assert counters["moe_prefill_pairs_moved"] == routed
    assert 0.8 < routed / (counters["prefill_tokens"] * 2 * 4) < 1.25
    tiles = counters["moe_prefill_tile_rows"]
    # a layer's one tile (64 or 128 rows) is worked for 1 to 8 experts
    assert tiles % 64 == 0 and routed <= tiles <= 8 * routed
