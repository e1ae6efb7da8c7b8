"""The benchmark's tests of ``moe_prefill_tile_rows_share``
(``benchmark/tests/test_tile_rows.py``: the manifest's entry, the reader on
fixtures and a tiny CPU rehearsal that brings the afmoe block's prefill
counters home), collected here so that the run that gates every PR guards
them too; in a file of their own, so that its rehearsal goes to another
worker than the others', and named to sort late, as
``test_tier1_benchmark_prefill_pairs.py`` is and for its reason (ROADMAP
D12: a rehearsal beside the first files of a ``--dist loadfile`` run took
the cores from the timing-sensitive tests that run meanwhile)."""

import pytest

pytest.register_assert_rewrite("benchmark.tests.test_tile_rows")

from benchmark.tests.test_tile_rows import *  # noqa: E402,F401,F403
