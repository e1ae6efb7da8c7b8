"""The afmoe architecture's benchmark tests (``benchmark/tests/test_afmoe.py``:
its files, costs, readers, comparison and tiny CPU rehearsal), collected here
so that the run that gates every PR guards them too; in a file of their own,
so that the rehearsals of ``test_benchmark_tier1.py`` and this one go to
different workers."""

import pytest

pytest.register_assert_rewrite("benchmark.tests.test_afmoe")

from benchmark.tests.test_afmoe import *  # noqa: E402,F401,F403


# ``benchmark/tests/test_afmoe.py`` holds the lists of its expert metrics
# EQUAL to its own cell; PR 38 appended a second cell to two of them (the
# contract allows that and nothing else) and may not edit a file the
# benchmark has. The test is the file's own, marked and not redefined: it
# passes again once a ``benchmark`` PR turns the equality into membership
# (PERF.md, section 7), and ``strict`` then says so here.
test_the_cell_its_files_and_its_metrics_are_found = pytest.mark.xfail(
    reason="asserts entry['workloads'] == [CELL]; PR 38 appended a cell",
    strict=True)(test_the_cell_its_files_and_its_metrics_are_found)  # noqa: F405
