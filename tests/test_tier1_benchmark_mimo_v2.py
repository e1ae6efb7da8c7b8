"""The mimo_v2 architecture's benchmark tests
(``benchmark/tests/test_mimo_v2.py``: its files, every catalog key, costs,
readers, comparison with its controls and tiny CPU rehearsal), collected
here so that the run that gates every PR guards them too; in a file of
their own, so that its rehearsal goes to another worker than the others',
and named to sort late, as ``test_tier1_benchmark_joyai.py`` is and for its
reason (ROADMAP D12, R0 ix)."""

import pytest

pytest.register_assert_rewrite("benchmark.tests.test_mimo_v2")

from benchmark.tests.test_mimo_v2 import *  # noqa: E402,F401,F403
