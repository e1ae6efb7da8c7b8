"""The benchmark's tests of the four readers of the program's compile log
(``benchmark/tests/test_compile_log.py``: the manifest's entries, their
explicit lists, each reader on made-up reports, and the tiny CPU rehearsal
that brings all five of PR 53's readings into one line), collected here so
that the run that gates every PR guards them too.

Two of them pinned the manifest as PR 53 left it: ten cells, and the five
entries the LAST of ``per_layer``. A cell or a metric appended since (PR 55:
an eleventh cell in all four lists, four entries after them; PR 57: a
twelfth cell, two entries) is what
``BENCHMARK.json`` is for, and a PR that may only add to the benchmark
cannot edit that file: the two are held here in the form that outlives an
append (every cell the manifest has, in its order; the five entries
together, in the issue's order). The file itself waits for a ``benchmark``
PR (PERF.md section 7)."""

import pytest

pytest.register_assert_rewrite("benchmark.tests.test_compile_log")

from benchmark.tests.test_compile_log import *  # noqa: E402,F401,F403
from benchmark.tests.test_compile_log import (  # noqa: E402
    METRICS, ROW_METRICS, manifest)


@pytest.mark.parametrize("metric", sorted(METRICS))
def test_the_manifest_gives_the_metric_to_every_cell_listed(man, metric):  # noqa: F811
    unit, source, moves = METRICS[metric]
    cells = [w["name"] for w in man["workloads"]]
    entry, = (m for m in man["per_layer"] if m["name"] == metric)
    assert entry == {
        "name": metric, "unit": unit, "better": "lower", "source": source,
        "layer": "generate unit", "moves": moves, "workloads": cells}
    assert len(entry["workloads"]) >= 10
    for name in entry["workloads"]:
        assert moves in {m["name"] for m in manifest.metrics_of(
            man, "end_to_end", name)}


def test_the_five_entries_are_the_last_of_per_layer_in_the_issues_order(man):  # noqa: F811
    names = [m["name"] for m in man["per_layer"]]
    at = names.index(ROW_METRICS[0])
    assert names[at:at + 5] == [
        *ROW_METRICS, "compiles_in_window", "warm_compile_s",
        "warm_trace_lower_s", "warm_cache_miss_share"]
    # what follows them was appended by later PRs, each for cells of its own
    assert all("workloads" in m for m in man["per_layer"][at + 5:])
