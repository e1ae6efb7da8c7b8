"""Rows the decode steps' live lanes held in both cache kinds over the
positions those lanes held: the program's ``(eva_window_rows_live +
eva_summary_rows_live) / eva_positions_live`` over the capture: what the
summaries save against a cache of one row a position. A lane at position
6,124 holds 1,014 + 319 rows: 22%. A change that reads summaries where it
should read exactly, or the reverse, moves it. None where the program has
no such counters."""
from benchmark import capture


def read(run):
    c = capture.counters(run)
    positions = c.get("eva_positions_live", 0)
    if positions <= 0 or "eva_window_rows_live" not in c:
        return None
    return 100.0 * (c["eva_window_rows_live"]
                    + c.get("eva_summary_rows_live", 0)) / positions
