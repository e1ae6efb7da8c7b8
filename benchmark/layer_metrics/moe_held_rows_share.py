"""(Lane, pick) pairs of the decode steps that landed on an expert this
chip holds over all the pairs its live lanes routed: the program's
``moe_rows_held / moe_rows_routed`` over the capture. A chip that holds a
quarter of a layer's experts reads 25% under uniform routing; a skewed
router moves it. None where the program has no such counters."""
from benchmark import capture


def read(run):
    c = capture.counters(run)
    routed = c.get("moe_rows_routed", 0)
    if routed <= 0 or "moe_rows_held" not in c:
        return None
    return 100.0 * c["moe_rows_held"] / routed
