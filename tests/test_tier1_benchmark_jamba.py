"""The jamba architecture's benchmark tests
(``benchmark/tests/test_jamba.py``: its files, every catalog key, costs at
the published sizes, readers, comparison with its controls and tiny CPU
rehearsal), collected here so that the run that gates every PR guards them
too; in a file of their own, so that its rehearsal goes to another worker
than the others', and named to sort late, as
``test_tier1_benchmark_joyai.py`` is and for its reason (ROADMAP D12, R0
ix).

One of them pinned the manifest as PR 55 left it: its cell the LAST of
``workloads`` and of every list it joined, its configuration the last of
``configs``. A cell appended since (PR 57) is what ``BENCHMARK.json`` is
for, and a PR that may only add to the benchmark cannot edit that file:
the test is held here in the form that outlives an append (the cell IN the
lists it joined, after every cell that was there before it), every other
assertion as it stands there. The file itself waits for a ``benchmark`` PR
(PERF.md section 7)."""

import json

import pytest

pytest.register_assert_rewrite("benchmark.tests.test_jamba")

from benchmark.tests.test_jamba import *  # noqa: E402,F401,F403
from benchmark.tests.test_jamba import (  # noqa: E402
    CELL, CONFIG, JOINED, NEW_METRICS, ROOT, manifest)


def test_the_cell_its_files_and_its_metrics_are_found(man, cfg, arch):  # noqa: F811
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
    # the eleventh cell and the ninth configuration, wherever later ones
    # were appended; in each list it joined, after the cells PR 55 found
    cells = [w["name"] for w in man["workloads"]]
    assert cells.index(CELL) == 10 and man["workloads"][10] == cell
    assert man["configs"][8]["name"] == CONFIG
    for name in JOINED:
        entry = next(m for m in man["per_layer"] if m["name"] == name)
        listed = entry["workloads"]
        assert CELL in listed
        assert all(cells.index(c) < 10 for c in listed[:listed.index(CELL)])
        assert all(cells.index(c) > 10 for c in listed[listed.index(CELL) + 1:])
    assert len(json.dumps(man)) < 64 << 10
