"""The benchmark's tests of the four readers of the program's compile log
(``benchmark/tests/test_compile_log.py``: the manifest's entries, their
explicit lists, each reader on made-up reports, and the tiny CPU rehearsal
that brings all five of PR 53's readings into one line), collected here so
that the run that gates every PR guards them too."""

import pytest

pytest.register_assert_rewrite("benchmark.tests.test_compile_log")

from benchmark.tests.test_compile_log import *  # noqa: E402,F401,F403
