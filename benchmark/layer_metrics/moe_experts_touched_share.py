"""Distinct routed experts a decode step touched, per expert layer, over
the experts the layer holds: the program's ``moe_experts_touched /
moe_layer_steps`` over the capture. The share of the expert weights a step
read; None where the program has no such counters."""
from benchmark import capture


def read(run):
    c = capture.counters(run)
    steps = c.get("moe_layer_steps", 0)
    if steps <= 0 or "moe_experts_touched" not in c:
        return None
    return 100.0 * c["moe_experts_touched"] / steps / run["config"]["num_experts"]
