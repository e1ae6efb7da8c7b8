"""``heartbeat_late_max_ms``: the manifest gives it to the five cells that
report ``tokens_per_s``, and its reader gives the latest beat of the
window's poll rows (``host.beat_late_s``: ``tracing.HostClock`` beside the
scheduler's ``PhaseClock``) on made-up reports, and nothing where the
program writes no such field (the parent of the PR that added it), no beat
was noted, the ring wrapped inside the window or there is no report. That
the rows carry the field is the program's to hold
(``tests/test_capture.py``); that a run's line carries the reading,
``test_compile_log.py``'s rehearsal. The row's ``runq_s`` and
``busy_share`` have no reader here: the machine the chips are on gives
neither file they are read from (PERF.md section 7). CPU only.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_host_rows.py -q
"""

from __future__ import annotations

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark import manifest  # noqa: E402

METRICS = {"heartbeat_late_max_ms": "ms"}
CELLS = ["mistral-7b-v0.3.batch", "internlm2-1.8b.batch",
         "trinity-mini.longbatch", "qwen3-next-80b-a3b.longbatch",
         "joyai-llm-flash.reasoning"]
WINDOW = (100.0, 140.0)


@pytest.fixture(scope="module")
def man():
    return manifest.load(ROOT)


def _row(seq, t, seconds, **host):
    row = {"type": "poll", "seq": seq, "t": t,
           "phase_s": {"read_wait": seconds * 0.75, "credit": seconds * 0.25}}
    if host:
        row["host"] = host
    return row


def _run(rows, t1=150.0):
    program = {"t0": 145.0, "t1": t1, "polls": rows}
    return {"window": WINDOW,
            "trace_counters": ({}, {"program": program})}


ROWS = [
    _row(0, 90.0, 9.9, runq_s=9.0, beat_late_s=9.0, busy_share=1.0),  # before
    _row(1, 100.0, 10.0, cpu_s=2.0, runq_s=0.004, beat_late_s=0.001,
         busy_share=0.5),
    {"type": "shed", "seq": 2, "t": 105.0, "reason": "queue_full"},
    _row(3, 110.0, 28.0, cpu_s=1.0, runq_s=2.6, beat_late_s=0.012,
         busy_share=0.25, gc_s=0.01),
    _row(4, 138.0, 2.0, cpu_s=0.1, runq_s=0.03, beat_late_s=3.1,
         busy_share=1.0),
    _row(5, 140.0, 5.0, runq_s=8.0, beat_late_s=8.0, busy_share=1.0),  # after
]
READINGS = {"heartbeat_late_max_ms": 3100.0}


@pytest.mark.parametrize("metric", sorted(METRICS))
def test_the_manifest_gives_the_metric_to_the_throughput_cells(man, metric):
    entry, = (m for m in man["per_layer"] if m["name"] == metric)
    assert entry == {
        "name": metric, "unit": METRICS[metric], "better": "lower",
        "source": "program_counter", "layer": "scheduler",
        "moves": "tokens_per_s", "workloads": CELLS}
    for cell in man["workloads"]:
        names = {m["name"] for m in manifest.metrics_of(
            man, "per_layer", cell["name"])}
        assert (metric in names) == (cell["name"] in CELLS)
    for name in CELLS:
        assert "tokens_per_s" in {m["name"] for m in manifest.metrics_of(
            man, "end_to_end", name)}
    # appended after the accepted entries
    names = [m["name"] for m in man["per_layer"]]
    assert names.index("kv_step_bytes_share") < names.index(metric)


@pytest.mark.parametrize("metric", sorted(METRICS))
def test_the_reader_reads_the_windows_rows(man, metric):
    read = manifest.layer_reader(ROOT, man, metric)
    assert read(_run(ROWS)) == pytest.approx(READINGS[metric])


def _without(field):
    return [dict(r, host={k: v for k, v in r["host"].items() if k != field})
            if "host" in r else r for r in ROWS]


FIELD = {"heartbeat_late_max_ms": "beat_late_s"}


@pytest.mark.parametrize("metric", sorted(METRICS))
def test_a_row_without_the_field_is_left_out_and_none_gives_none(man, metric):
    read = manifest.layer_reader(ROOT, man, metric)
    # the field on one row of the window alone: that row's reading
    rows = _without(FIELD[metric])
    rows[3] = ROWS[3]
    alone = {"heartbeat_late_max_ms": 12.0}
    assert read(_run(rows)) == pytest.approx(alone[metric])
    # on none (no beat yet)
    assert read(_run(_without(FIELD[metric]))) is None
    # rows of a program before the field
    assert read(_run([{k: v for k, v in r.items() if k != "host"}
                      for r in ROWS])) is None


@pytest.mark.parametrize("metric", sorted(METRICS))
@pytest.mark.parametrize("run", [
    _run([dict(r, seq=r["seq"] + 7000, t=r["t"] + 15.0) for r in ROWS]),
    _run([]), _run([ROWS[0], ROWS[5]]),
    {"window": WINDOW, "trace_counters": ({}, {})},
    {"window": WINDOW, "trace_counters": None}],
    ids=["a_ring_that_wrapped_in_the_window", "no_rows", "none_in_the_window",
         "no_report", "no_capture"])
def test_the_reader_gives_nothing_without_the_windows_rows(man, metric, run, capsys):
    assert manifest.layer_reader(ROOT, man, metric)(run) is None
    capsys.readouterr()
