"""(Lane, pick) pairs a decode step routed over the distinct experts it
touched: the rows each expert it read was read for. The program's
``moe_rows_routed / moe_experts_touched`` over the capture; None where the
program has no such counters."""
from benchmark import capture


def read(run):
    c = capture.counters(run)
    touched = c.get("moe_experts_touched", 0)
    if touched <= 0 or "moe_rows_routed" not in c:
        return None
    return c["moe_rows_routed"] / touched
