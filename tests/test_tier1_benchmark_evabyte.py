"""The evabyte architecture's benchmark tests
(``benchmark/tests/test_evabyte.py``: its files, every catalog key, costs,
readers, comparison with its controls and tiny CPU rehearsal), collected
here so that the run that gates every PR guards them too; in a file of
their own, so that its rehearsal goes to another worker than the others',
and named to sort late, as ``test_tier1_benchmark_joyai.py`` is and for its
reason (ROADMAP D12: a rehearsal beside the first files of a ``--dist
loadfile`` run took the cores from the timing-sensitive tests that run
meanwhile)."""

import pytest

pytest.register_assert_rewrite("benchmark.tests.test_evabyte")

from benchmark.tests.test_evabyte import *  # noqa: E402,F401,F403
