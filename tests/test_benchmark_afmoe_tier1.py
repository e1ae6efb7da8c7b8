"""The afmoe architecture's benchmark tests (``benchmark/tests/test_afmoe.py``:
its files, costs, readers, comparison and tiny CPU rehearsal), collected here
so that the run that gates every PR guards them too; in a file of their own,
so that the rehearsals of ``test_benchmark_tier1.py`` and this one go to
different workers."""

import pytest

pytest.register_assert_rewrite("benchmark.tests.test_afmoe")

from benchmark.tests.test_afmoe import *  # noqa: E402,F401,F403
