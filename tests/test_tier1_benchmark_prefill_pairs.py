"""The benchmark's tests of ``moe_prefill_pairs_moved_share``
(``benchmark/tests/test_prefill_pairs.py``: the manifest's entry, the
reader on fixtures and a tiny CPU rehearsal that brings the prefills'
counters home), collected here so that the run that gates every PR guards
them too; in a file of their own, so that its rehearsal goes to another
worker than the others'. Named to sort late, unlike the other
``test_benchmark_*_tier1.py``: those three rehearse from the start of a
``--dist loadfile`` run, and a fourth beside them took the cores from the
timing-sensitive seeded-sampling tests that run meanwhile (ROADMAP D12:
``test_planning.py``'s retune test failed in 2 of 3 whole runs with this
file among them, in 0 of 2 on the parent)."""

import pytest

pytest.register_assert_rewrite("benchmark.tests.test_prefill_pairs")

from benchmark.tests.test_prefill_pairs import *  # noqa: E402,F401,F403


# ``benchmark/tests/test_prefill_pairs.py`` holds its entry to be the LAST of
# ``per_layer``; PR 40 appended four entries after it (new entries go to the
# end of their lists) and may not edit a file the benchmark has. So the test
# is restated here under its own name, every assertion but that one line, and
# stays live: a ``benchmark`` PR drops the line there and this copy with it
# (PERF.md, section 7). PR 42 appended a second cell whose chip holds a
# share of its experts and whose prefills count the same two counters
# (``joyai-llm-flash.reasoning``): the metric is held to those two cells,
# the first its own.
CELLS = [CELL, "joyai-llm-flash.reasoning"]  # noqa: F405


def test_the_manifest_gives_the_metric_to_the_one_cell(man):  # noqa: F811
    entry, = (m for m in man["per_layer"] if m["name"] == METRIC)  # noqa: F405
    assert entry == {
        "name": METRIC, "unit": "%", "better": "lower",  # noqa: F405
        "source": "program_counter", "layer": "model step",
        "moves": "tokens_per_s", "workloads": CELLS}
    for cell in man["workloads"]:
        names = {m["name"] for m in manifest.metrics_of(  # noqa: F405
            man, "per_layer", cell["name"])}
        assert (METRIC in names) == (cell["name"] in CELLS)  # noqa: F405
    for name in CELLS:
        assert "tokens_per_s" in {m["name"] for m in manifest.metrics_of(  # noqa: F405
            man, "end_to_end", name)}
