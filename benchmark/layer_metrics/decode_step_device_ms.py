"""Device time of the decode-burst executable over the steps it ran."""
from benchmark import trace

BURST = "jit_fused_burst"


def read(run):
    seconds, runs = trace.module_seconds(run["trace"] or {}, BURST)
    if not runs:
        return None
    return 1e3 * seconds / (runs * run["steps_per_burst"])
