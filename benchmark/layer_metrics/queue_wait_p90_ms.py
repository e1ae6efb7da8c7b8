"""The scheduler's submit-to-admit wait (``gen_queue_wait_ms``' samples) of
the requests completed in the window."""
from benchmark import endtoend


def read(run):
    waits = [q * 1e3 for q, _ttft, _t in run["slo"]]
    return endtoend.percentile(waits, 90) if waits else None
