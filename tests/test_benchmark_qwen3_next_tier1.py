"""The qwen3_next architecture's benchmark tests
(``benchmark/tests/test_qwen3_next.py``: its files, costs, readers,
comparison with its controls and tiny CPU rehearsal), collected here so
that the run that gates every PR guards them too; in a file of their own,
so that its rehearsal goes to another worker than the others'."""

import pytest

pytest.register_assert_rewrite("benchmark.tests.test_qwen3_next")

from benchmark.tests.test_qwen3_next import *  # noqa: E402,F401,F403
