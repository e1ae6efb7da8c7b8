"""The benchmark's tests of the reader of a poll row's ``host``
(``benchmark/tests/test_host_rows.py``: the manifest's entry, its explicit
list, and the reader on made-up reports), collected here so that the run
that gates every PR guards them too."""

import pytest

pytest.register_assert_rewrite("benchmark.tests.test_host_rows")

from benchmark.tests.test_host_rows import *  # noqa: E402,F401,F403
