"""(Row, pick) pairs that the prefills' grouped experts moved (gathered,
multiplied, weighted and summed) over the pairs those prefills routed: the
program's ``moe_prefill_pairs_moved / moe_prefill_pairs_routed`` over the
capture. A chip that holds a share of a layer's experts moves a room of
pairs a pass (``ops/experts.py:room_of``: 5/16 of them where it holds a
quarter), so 31-32% while one pass does, more where a skewed router makes
the drop-free fall-back run again; 100% where every pair is moved. None
where the program has no such counters."""
from benchmark import capture


def read(run):
    c = capture.counters(run)
    routed = c.get("moe_prefill_pairs_routed", 0)
    if routed <= 0 or "moe_prefill_pairs_moved" not in c:
        return None
    return 100.0 * c["moe_prefill_pairs_moved"] / routed
