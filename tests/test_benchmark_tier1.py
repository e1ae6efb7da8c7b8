"""The benchmark's own fast tests (``benchmark/tests/test_benchmark.py``:
manifest, traffic, records to metrics, trace reduction, costs, the plain
reference, and the tiny CPU rehearsals of ``benchmark/run.py`` under every
``--trace`` flag), collected here so that the run that gates every PR also
guards the harness."""

import pytest

pytest.register_assert_rewrite("benchmark.tests.test_benchmark")

from benchmark.tests.test_benchmark import *  # noqa: E402,F401,F403
