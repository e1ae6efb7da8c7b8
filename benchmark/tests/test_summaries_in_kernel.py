"""``eva_summaries_in_kernel_share``: the manifest gives it to the evabyte
cell alone, its reader gives the share on made-up captures and nothing
where the program has no such counter (the parent of the PR that added it,
a family that names none) or the capture's steps completed no chunk, and a
tiny CPU rehearsal of ``evabyte`` brings the batcher's counter through the
bursts into ``capture.json`` and the metric into the line: 0 there, where
the model's step scatters the rows (the kernel runs on a TPU only). CPU
only.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_summaries_in_kernel.py -q
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

METRIC = "eva_summaries_in_kernel_share"
CONFIG = "evabyte"
CELLS = ["evabyte.bytebatch"]


@pytest.fixture(scope="module")
def man():
    return manifest.load(ROOT)


def _run(counters):
    return {"trace_counters": ({}, {"program": {"counters": counters}})}


def test_the_manifest_gives_the_metric_to_the_evabyte_cell_alone(man):
    entry, = (m for m in man["per_layer"] if m["name"] == METRIC)
    assert entry == {
        "name": METRIC, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "kernels",
        "moves": "tpot_p50_ms", "workloads": CELLS}
    for cell in man["workloads"]:
        names = {m["name"] for m in manifest.metrics_of(
            man, "per_layer", cell["name"])}
        assert (METRIC in names) == (cell["name"] in CELLS)
    for name in CELLS:
        assert "tpot_p50_ms" in {m["name"] for m in manifest.metrics_of(
            man, "end_to_end", name)}


@pytest.mark.parametrize("in_kernel,written,share", [
    (5_880, 5_880, 100.0),      # 368 steps of 20 lanes over 8 layers / 16, a TPU
    (2_940, 5_880, 50.0),       # half of them: made up
    (0, 5_880, 0.0),            # the scatters wrote them all: off a TPU
    (8, 8, 100.0),              # one lane ended one chunk
])
def test_the_reader_gives_the_rows_the_kernel_wrote_over_the_rows_written(
        man, in_kernel, written, share):
    read = manifest.layer_reader(ROOT, man, METRIC)
    assert read(_run({"eva_summaries_written_in_kernel": in_kernel,
                      "eva_summaries_written": written,
                      "eva_lane_steps": 16 * written})) == pytest.approx(share)


@pytest.mark.parametrize("run", [
    _run({}), _run({"tokens": 5, "eva_lane_steps": 9}),
    _run({"eva_summaries_written": 40, "eva_lane_steps": 640}),
    _run({"eva_summaries_written_in_kernel": 0, "eva_summaries_written": 0}),
    _run({"eva_summaries_written_in_kernel": 7}),
    {"trace_counters": None}, {}],
    ids=["no_counters", "other_counters", "the_parents_counter_alone",
         "no_chunk_completed_in_the_capture", "half_of_them", "no_capture",
         "no_run"])
def test_the_reader_finds_nothing_without_the_counter(man, run):
    assert manifest.layer_reader(ROOT, man, METRIC)(run) is None


def test_the_counter_reaches_the_capture_and_the_metric_the_line(tmp_path):
    """The configuration under a tiny mix in a copy (chunks of 4 bytes, so
    every request's steps complete chunks): the family's step counter and
    the batcher's mirror of it both come home with the bursts, and off a
    TPU the mirror stays 0."""
    bench, man = _copy_of_the_benchmark(tmp_path)
    mix = dict(TINY_MIX, classes=[[20, 8, 1], [40, 16, 1]])
    (bench / "traffic" / "tiny.json").write_text(json.dumps(mix))
    man["workloads"].append({"name": CONFIG + ".tiny", "config": CONFIG,
                             "traffic": "tiny", "chips": 1, "why": "test"})
    for m in man["per_layer"]:
        if m["name"] == METRIC:
            m["workloads"].append(CONFIG + ".tiny")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(man))
    _out, line = _rehearse(tmp_path, CONFIG + ".tiny", "2")
    assert line["correct"] and line["failed"] == 0 and line["attempted"] > 0
    assert line["metrics"][METRIC] == {"value": 0.0, "unit": "%"}
    run_dir, = (bench / "_runs" / (CONFIG + ".tiny")).glob("*-trace2-0")
    counters = json.load(open(run_dir / "capture.json"))["counters"]
    assert counters["eva_summaries_written"] > 0
    assert counters["eva_summaries_written_in_kernel"] == 0
