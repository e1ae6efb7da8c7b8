"""``gdn_prefill_chunks_walked_share``: the manifest gives it to the one cell
whose model has Gated DeltaNet layers, its reader gives the share on a
fixture and nothing where the program has no such counters (the parent of
the PR that added them, a family that names none), and a tiny CPU rehearsal
of the cell's configuration brings the two counters from the prefills
through the bursts into ``capture.json`` and the metric into the line.
CPU only.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_gdn_chunks.py -q
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

METRIC = "gdn_prefill_chunks_walked_share"
CONFIG = "qwen3-next-80b-a3b"
CELL = CONFIG + ".longbatch"
# prompts of 20 tokens (the one chunk a 32 bucket is padded to) and of 150
# (three of a 512 bucket's eight): a rehearsal's capture walks between 37.5
# and 100%
SHORT_OF_ITS_BUCKET = dict(TINY_MIX, classes=[[20, 8, 1], [150, 8, 1]])


@pytest.fixture(scope="module")
def man():
    return manifest.load(ROOT)


def _run(counters):
    return {"trace_counters": ({}, {"program": {"counters": counters}})}


def test_the_manifest_gives_the_metric_to_the_one_cell(man):
    entry, = (m for m in man["per_layer"] if m["name"] == METRIC)
    assert entry == {
        "name": METRIC, "unit": "%", "better": "lower",
        "source": "program_counter", "layer": "kernels",
        "moves": "tokens_per_s", "workloads": [CELL]}
    for cell in man["workloads"]:
        names = {m["name"] for m in manifest.metrics_of(
            man, "per_layer", cell["name"])}
        assert (METRIC in names) == (cell["name"] == CELL)
    assert "tokens_per_s" in {m["name"] for m in manifest.metrics_of(
        man, "end_to_end", CELL)}


def test_the_longbatch_mix_walks_188_of_276_chunks_a_cycle(man):
    """The arithmetic the metric's 68.1% stands on, from the mix's own file,
    the batcher's default buckets and the configuration's ``max_seq``."""
    import inspect

    from seldon_core_tpu.serving.continuous import ContinuousBatcher

    buckets = inspect.signature(ContinuousBatcher.__init__).parameters[
        "prefill_buckets"].default
    max_seq = manifest.config(ROOT, man, CONFIG)["server"]["max_seq"]
    walked = bucket = 0
    for prompt, _new, count in manifest.traffic(ROOT, man, "longbatch")["classes"]:
        padded = min([b for b in buckets if prompt <= b] + [max_seq])
        walked += count * -(-prompt // 64)
        bucket += count * padded // 64
    assert (walked, bucket) == (188, 276)


@pytest.mark.parametrize("walked,bucket,share", [
    (188 * 6, 276 * 6, 100 * 188 / 276),         # a cycle of the longbatch mix
    (32 * 6, 64 * 6, 50.0),                      # 2048 tokens in the 4096 bucket
    (64, 64, 100.0),                             # a prompt that fills its bucket
    (0, 12, 0.0),                                # lens of 0: nothing walked
])
def test_the_reader_gives_the_share_of_the_chunks_walked(man, walked, bucket, share):
    read = manifest.layer_reader(ROOT, man, METRIC)
    assert read(_run({"gdn_prefill_chunks_walked": walked,
                      "gdn_prefill_chunks_bucket": bucket})) == pytest.approx(share)


@pytest.mark.parametrize("run", [
    _run({}), _run({"tokens": 5, "moe_prefill_pairs_routed": 9}),
    _run({"gdn_prefill_chunks_walked": 0, "gdn_prefill_chunks_bucket": 0}),
    _run({"gdn_prefill_chunks_bucket": 7}),
    {"trace_counters": None}, {}],
    ids=["no_counters", "other_counters", "no_prefill_in_the_capture",
         "half_of_them", "no_capture", "no_run"])
def test_the_reader_finds_nothing_without_the_counters(man, run):
    assert manifest.layer_reader(ROOT, man, METRIC)(run) is None


def test_the_counters_reach_the_capture_and_the_metric_the_line(tmp_path):
    """The cell's configuration under a tiny mix in a copy: one period of
    four layers, three of them linear."""
    bench, man = _copy_of_the_benchmark(tmp_path)
    (bench / "traffic" / "tiny.json").write_text(json.dumps(SHORT_OF_ITS_BUCKET))
    man["workloads"].append({"name": CONFIG + ".tiny", "config": CONFIG,
                             "traffic": "tiny", "chips": 1, "why": "test"})
    for m in man["per_layer"]:
        if m["name"] == METRIC:
            m["workloads"].append(CONFIG + ".tiny")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(man))
    _out, line = _rehearse(tmp_path, CONFIG + ".tiny", "2")
    assert line["correct"] and line["failed"] == 0 and line["attempted"] > 0
    assert line["metrics"][METRIC]["unit"] == "%"
    assert 37.5 <= line["metrics"][METRIC]["value"] < 100.0
    run_dir, = (bench / "_runs" / (CONFIG + ".tiny")).glob("*-trace2-0")
    counters = json.load(open(run_dir / "capture.json"))["counters"]
    walked = counters["gdn_prefill_chunks_walked"]
    bucket = counters["gdn_prefill_chunks_bucket"]
    # in each of 3 linear layers a prefill of the short class walks 1 chunk
    # of 1 and one of the long class 3 of 8: whole numbers of each came home
    long_ones, rest = divmod(bucket - walked, 3 * (8 - 3))
    assert long_ones > 0 and rest == 0
    short_ones, rest = divmod(walked - 3 * 3 * long_ones, 3)
    assert short_ones > 0 and rest == 0
