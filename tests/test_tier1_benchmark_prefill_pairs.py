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
