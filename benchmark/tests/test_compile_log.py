"""``compiles_in_window``, ``warm_compile_s``, ``warm_trace_lower_s``,
``warm_cache_miss_share``: the manifest gives them to all ten cells, listed,
and their readers give what the program's compile log says
(``tracing.CompileLog``, relayed in the capture's report as ``compiles``)
on made-up reports, and nothing on a report without the log (the parent of
the PR that added it) or without a report. One tiny CPU rehearsal under
``--trace 2`` brings all five of the PR's readings into the line, with
the rows and the log they were read from in ``capture.json``. CPU only.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_compile_log.py -q
"""

from __future__ import annotations

import json
import os
import re
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark import manifest  # noqa: E402
from benchmark.tests.test_benchmark import (  # noqa: E402
    TINY_MIX, _copy_of_the_benchmark, _rehearse,
)

METRICS = {
    "compiles_in_window": ("compiles", "program_counter", "tpot_p50_ms"),
    "warm_compile_s": ("s", "program_span", "setup_s"),
    "warm_trace_lower_s": ("s", "program_span", "setup_s"),
    "warm_cache_miss_share": ("%", "program_counter", "setup_s"),
}
ROW_METRICS = ("heartbeat_late_max_ms",)
WINDOW = (100.0, 140.0)


@pytest.fixture(scope="module")
def man():
    return manifest.load(ROOT)


def _totals(n, trace_s, lower_s, backend_s, hits, misses):
    return {"n": n, "trace_s": trace_s, "lower_s": lower_s,
            "backend_s": backend_s, "cache_hits": hits, "cache_misses": misses}


def _event(t, kind, name="jit_prefill_one", s=0.5):
    return {"t": t, "name": name, "kind": kind, "s": s,
            "cache": "miss" if kind == "backend" else None}


LOG = {
    "stages": {"load": _totals(10, 0.1, 0.4, 1.5, 7, 3),
               "warm": _totals(27, 3.0, 5.5, 21.25, 26, 1),
               "serve": _totals(90, 0.2, 0.6, 3.2, 88, 2)},
    "executables": [dict(_totals(1, 1.0, 2.0, 9.0, 1, 0), stage="warm",
                         name="jit_fused_burst")],
    "serve_events": [
        _event(99.9, "backend"),                          # before the window
        _event(100.0, "backend"),
        _event(120.0, "trace"), _event(120.1, "lower"), _event(121.0, "backend"),
        _event(139.9, "backend", "jit_insert", 0.01),     # under a second too
        _event(140.0, "backend"), _event(151.0, "backend"),     # after it
    ],
}
READINGS = {"compiles_in_window": 3, "warm_compile_s": 21.25,
            "warm_trace_lower_s": 8.5,
            "warm_cache_miss_share": 100.0 * 4 / 37}


def _run(log):
    program = {"t0": 145.0, "t1": 150.0, "polls": []}
    if log is not None:
        program["compiles"] = log
    return {"window": WINDOW, "trace_counters": ({}, {"program": program})}


@pytest.mark.parametrize("metric", sorted(METRICS))
def test_the_manifest_gives_the_metric_to_every_cell_listed(man, metric):
    unit, source, moves = METRICS[metric]
    cells = [w["name"] for w in man["workloads"]]
    entry, = (m for m in man["per_layer"] if m["name"] == metric)
    assert entry == {
        "name": metric, "unit": unit, "better": "lower", "source": source,
        "layer": "generate unit", "moves": moves, "workloads": cells[:10]}
    assert len(entry["workloads"]) == 10
    for name in entry["workloads"]:
        assert moves in {m["name"] for m in manifest.metrics_of(
            man, "end_to_end", name)}


def test_the_five_entries_are_the_last_of_per_layer_in_the_issues_order(man):
    assert [m["name"] for m in man["per_layer"]][-5:] == [
        *ROW_METRICS, "compiles_in_window", "warm_compile_s",
        "warm_trace_lower_s", "warm_cache_miss_share"]


@pytest.mark.parametrize("metric", sorted(METRICS))
def test_the_reader_reads_the_log(man, metric):
    value = manifest.layer_reader(ROOT, man, metric)(_run(LOG))
    assert value == pytest.approx(READINGS[metric])
    assert value is not None and not isinstance(value, bool)


@pytest.mark.parametrize("metric", sorted(METRICS))
@pytest.mark.parametrize("run", [
    _run(None), {"window": WINDOW, "trace_counters": ({}, {})},
    {"window": WINDOW, "trace_counters": None}],
    ids=["a_report_without_compiles", "no_report", "no_capture"])
def test_the_reader_gives_nothing_without_the_log(man, metric, run):
    assert manifest.layer_reader(ROOT, man, metric)(run) is None


def test_a_window_with_no_compile_reads_zero_not_nothing(man):
    quiet = dict(LOG, serve_events=[e for e in LOG["serve_events"]
                                    if not 100.0 <= e["t"] < 140.0])
    assert manifest.layer_reader(ROOT, man, "compiles_in_window")(_run(quiet)) == 0
    assert manifest.layer_reader(ROOT, man, "compiles_in_window")(
        _run(dict(LOG, serve_events=[]))) == 0


def test_a_ring_that_wrapped_reads_what_it_still_holds(man):
    """256 events, the oldest inside the window: at least these compiled."""
    ring = [_event(110.0 + i * 0.01, "backend") for i in range(256)]
    assert manifest.layer_reader(ROOT, man, "compiles_in_window")(
        _run(dict(LOG, serve_events=ring))) == 256


def test_a_log_without_the_stage_or_with_the_cache_off(man):
    no_warm = dict(LOG, stages={"load": LOG["stages"]["load"]})
    for metric in ("warm_compile_s", "warm_trace_lower_s"):
        assert manifest.layer_reader(ROOT, man, metric)(_run(no_warm)) is None
    # the load stage alone still says what the cache held
    assert manifest.layer_reader(ROOT, man, "warm_cache_miss_share")(
        _run(no_warm)) == pytest.approx(30.0)
    cache_off = dict(LOG, stages={
        s: dict(v, cache_hits=0, cache_misses=0) for s, v in LOG["stages"].items()})
    assert manifest.layer_reader(ROOT, man, "warm_cache_miss_share")(
        _run(cache_off)) is None
    assert manifest.layer_reader(ROOT, man, "warm_compile_s")(
        _run(cache_off)) == 21.25


def test_the_cpu_rehearsal_of_a_dense_cell_prints_all_five(tmp_path):
    """``--rehearse-cpu --trace 2`` of a dense cell under a tiny mix: the
    five readings are in the line, every poll row of ``capture.json``
    carries ``host``, the thread's seconds never exceed the rows' span, the
    log's warm seconds lie inside the ready line's ``warm_s``, and the
    ready line carries the four new fields after ``warm_s=``."""
    bench, man = _copy_of_the_benchmark(tmp_path)
    cfg = json.load(open(bench / "configs" / "internlm2-1.8b.json"))
    cfg["server"]["slots"] = 2
    (bench / "configs" / "added.json").write_text(json.dumps(cfg))
    (bench / "traffic" / "tiny.json").write_text(json.dumps(TINY_MIX))
    man["configs"].append({"name": "added", "source": "test", "reduced": [],
                           "file": "benchmark/configs/added.json", "why": "test"})
    man["workloads"].append({"name": "added.tiny", "config": "added",
                             "traffic": "tiny", "chips": 1, "why": "test"})
    for m in man["per_layer"]:
        if m["name"] in (*METRICS, *ROW_METRICS):
            m["workloads"] = m["workloads"] + ["added.tiny"]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(man))
    _out, line = _rehearse(tmp_path, "added.tiny", "2")
    got = line["metrics"]
    assert set(METRICS) | set(ROW_METRICS) <= set(got)
    assert got["compiles_in_window"] == {"value": 0, "unit": "compiles"}
    assert got["warm_cache_miss_share"]["value"] == 100.0     # a cache of its own
    assert got["heartbeat_late_max_ms"]["value"] >= 0.0
    run_dir, = (bench / "_runs" / "added.tiny").glob("*")
    report = json.load(open(run_dir / "capture.json"))
    rows = [r for r in report["polls"] if r["type"] == "poll"]
    assert rows and all({"cpu_s", "beat_late_s"} <= set(r["host"]) for r in rows)
    span = rows[-1]["t"] + sum(rows[-1]["phase_s"].values()) - rows[0]["t"]
    assert sum(r["host"]["cpu_s"] + r["host"].get("runq_s", 0.0)
               for r in rows) <= span + 0.02
    warm_s = got["warm_s"]["value"]
    assert 0.0 < got["warm_compile_s"]["value"] + got["warm_trace_lower_s"]["value"] <= (
        warm_s + 0.05)      # the ready line rounds to a tenth
    ready, = (ln for ln in open(run_dir / "engine.log") if " ready (" in ln)
    said = re.search(r" warm_s=([\d.]+) warm_trace_lower_s=([\d.]+) "
                     r"warm_compile_s=([\d.]+) cache_hits=(\d+) "
                     r"cache_misses=(\d+)$", ready.strip())
    assert said and float(said[1]) == warm_s
    assert float(said[3]) == pytest.approx(got["warm_compile_s"]["value"], abs=0.051)
    assert int(said[4]) == 0 and int(said[5]) > 0
