"""The benchmark's tests of ``block_rows_read_share``
(``benchmark/tests/test_block_rows_read_share.py``: the manifest's entry
and the reader on made-up captures), collected here so that the run that
gates every PR guards them too."""

import pytest

pytest.register_assert_rewrite("benchmark.tests.test_block_rows_read_share")

from benchmark.tests.test_block_rows_read_share import *  # noqa: E402,F401,F403


# ``benchmark/tests/test_block_rows_read_share.py`` holds the metric's entry
# to be the LAST of ``per_layer``; PR 51 appended three after it (new
# entries go to the end of their lists) and may not edit a file the
# benchmark has. So the test is restated here under its own name without
# that line, and stays live: a ``benchmark`` PR drops the line there and
# this copy with it (PERF.md, section 7 f).
def test_the_manifest_gives_the_metric_to_the_sdar_cell_alone(man):  # noqa: F811
    entry, = (m for m in man["per_layer"] if m["name"] == METRIC)  # noqa: F405
    assert entry == {
        "name": METRIC, "unit": "%", "better": "lower",  # noqa: F405
        "source": "program_counter", "layer": "kernels",
        "moves": "tpot_p50_ms", "workloads": CELLS}  # noqa: F405
    for cell in man["workloads"]:
        names = {m["name"] for m in manifest.metrics_of(  # noqa: F405
            man, "per_layer", cell["name"])}
        assert (METRIC in names) == (cell["name"] in CELLS)  # noqa: F405
    for name in CELLS:  # noqa: F405
        assert "tpot_p50_ms" in {m["name"] for m in manifest.metrics_of(  # noqa: F405
            man, "end_to_end", name)}
