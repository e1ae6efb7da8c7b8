"""``moe_prefill_pairs_moved_share``: the manifest gives it to the one cell
whose chip holds a share of its experts, its reader gives the share on a
fixture and nothing where the program has no such counters (the parent of
the PR that added them, a family that names none), and a tiny CPU rehearsal
of the cell's configuration brings the two counters from the prefills
through the bursts into ``capture.json`` and the metric into the line.
CPU only.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_prefill_pairs.py -q
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

METRIC = "moe_prefill_pairs_moved_share"
CONFIG = "qwen3-next-80b-a3b"
CELL = CONFIG + ".longbatch"


@pytest.fixture(scope="module")
def man():
    return manifest.load(ROOT)


def _run(counters):
    return {"trace_counters": ({}, {"program": {"counters": counters}})}


def test_the_manifest_gives_the_metric_to_the_one_cell(man):
    entry, = (m for m in man["per_layer"] if m["name"] == METRIC)
    assert entry == {
        "name": METRIC, "unit": "%", "better": "lower",
        "source": "program_counter", "layer": "model step",
        "moves": "tokens_per_s", "workloads": [CELL]}
    assert man["per_layer"][-1] is entry          # added at the end
    for cell in man["workloads"]:
        names = {m["name"] for m in manifest.metrics_of(
            man, "per_layer", cell["name"])}
        assert (METRIC in names) == (cell["name"] == CELL)
    assert "tokens_per_s" in {m["name"] for m in manifest.metrics_of(
        man, "end_to_end", CELL)}


@pytest.mark.parametrize("moved,routed,share", [
    (12_928 * 8, 40_960 * 8, 31.5625),           # a 4096-bucket prompt
    (56_320, 176_640, 100 * 56_320 / 176_640),   # a cycle of the longbatch mix
    (40_960, 40_960, 100.0),                     # every pair moved
    (4 * 12_928, 40_960, 126.25),                # the fall-back's four passes
])
def test_the_reader_gives_the_share_of_the_pairs_moved(man, moved, routed, share):
    read = manifest.layer_reader(ROOT, man, METRIC)
    assert read(_run({"moe_prefill_pairs_moved": moved,
                      "moe_prefill_pairs_routed": routed})) == pytest.approx(share)


@pytest.mark.parametrize("run", [
    _run({}), _run({"tokens": 5, "moe_rows_routed": 9}),
    _run({"moe_prefill_pairs_moved": 0, "moe_prefill_pairs_routed": 0}),
    _run({"moe_prefill_pairs_routed": 7}),
    {"trace_counters": None}, {}],
    ids=["no_counters", "other_counters", "no_prefill_in_the_capture",
         "half_of_them", "no_capture", "no_run"])
def test_the_reader_finds_nothing_without_the_counters(man, run):
    assert manifest.layer_reader(ROOT, man, METRIC)(run) is None


def test_the_counters_reach_the_capture_and_the_metric_the_line(tmp_path):
    """The cell's configuration under a tiny mix in a copy: 4 of 16 experts
    held and prompts of 20 and 40 tokens, whose rooms round up to 128 pairs
    (all of a 32-token bucket's, half of a 64-token one's): under 100%,
    well over the 31% of a long prompt."""
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
    assert 31.25 < line["metrics"][METRIC]["value"] < 100.0
    run_dir, = (bench / "_runs" / (CONFIG + ".tiny")).glob("*-trace2-0")
    counters = json.load(open(run_dir / "capture.json"))["counters"]
    # every prefill of a 32- or 64-token bucket routes 4 picks in 4 layers;
    # its counters come home a burst after ``prefill_tokens`` counted it,
    # so the two differ by the prefills at the capture's edges
    routed = counters["moe_prefill_pairs_routed"]
    assert routed > 0 and routed % (32 * 4 * 4) == 0
    assert 0.8 < routed / (counters["prefill_tokens"] * 4 * 4) < 1.25
    assert 0.3125 * routed < counters["moe_prefill_pairs_moved"] < routed
    assert counters["moe_prefill_pairs_moved"] % 128 == 0
