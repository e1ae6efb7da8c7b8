"""The benchmark's tests of ``eva_summaries_in_kernel_share``
(``benchmark/tests/test_summaries_in_kernel.py``: the manifest's entry, the
reader on made-up captures and a tiny CPU rehearsal that brings the
batcher's counter home), collected here so that the run that gates every PR
guards them too; in a file of their own, so that its rehearsal goes to
another worker than the others', and named to sort late, as
``test_tier1_benchmark_tile_rows.py`` is and for its reason (ROADMAP D12: a
rehearsal beside the first files of a ``--dist loadfile`` run took the cores
from the timing-sensitive tests that run meanwhile)."""

import pytest

pytest.register_assert_rewrite("benchmark.tests.test_summaries_in_kernel")

from benchmark.tests.test_summaries_in_kernel import *  # noqa: E402,F401,F403
