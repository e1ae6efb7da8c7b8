"""Lane-steps that yielded a token a request kept over lane-steps
dispatched, from the batcher's counters at the window's edges. A request's
first token comes from its prefill, not from a lane-step, so one token per
admitted request is left out."""


def read(run):
    a, b = (s["stats"] for s in run["counters"])
    lane_steps = b["lane_steps"] - a["lane_steps"]
    if lane_steps <= 0:
        return None
    tokens = (b["tokens"] - a["tokens"]) - (b["admitted"] - a["admitted"])
    return 100.0 * tokens / lane_steps
