"""(Lane, pick) pairs of the decode steps that landed on an expert this
chip holds over the distinct held experts those steps touched: the rows
each expert it read was read for, where the chip holds a share of the
layer's experts. The program's ``moe_rows_held / moe_experts_touched``
over the capture (``moe_rows_per_touched_expert`` divides ALL routed
pairs by the held experts touched, which is this only where every expert
is held). None where the program has no such counters."""
from benchmark import capture


def read(run):
    c = capture.counters(run)
    touched = c.get("moe_experts_touched", 0)
    if touched <= 0 or "moe_rows_held" not in c:
        return None
    return c["moe_rows_held"] / touched
