"""Rows of both cache kinds the decode steps streamed over the rows their
live lanes' positions admitted: the program's ``eva_rows_read /
(eva_window_rows_live + eva_summary_rows_live)`` over the capture. The
ragged read rounds each kind up to its block of 128 (two kinds, two
roundings a lane; a window's summaries are whole blocks, so in effect the
ring's last block): 100% is a read with no rounding. None where the
program has no such counters."""
from benchmark import capture


def read(run):
    c = capture.counters(run)
    live = c.get("eva_window_rows_live", 0) + c.get("eva_summary_rows_live", 0)
    if live <= 0 or "eva_rows_read" not in c:
        return None
    return 100.0 * c["eva_rows_read"] / live
