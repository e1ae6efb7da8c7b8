"""Positions the window layers streamed (from the block that holds each
lane's window's start) over every live position of those lanes: the
program's ``kv_positions_read_window / kv_positions_live_window`` over the
capture. 100% is a window layer that reads like a full one; None where the
program has no such counters."""
from benchmark import capture


def read(run):
    c = capture.counters(run)
    live = c.get("kv_positions_live_window", 0)
    if live <= 0 or "kv_positions_read_window" not in c:
        return None
    return 100.0 * c["kv_positions_read_window"] / live
