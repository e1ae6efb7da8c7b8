"""The share of the scheduler thread's time it works rather than waits:
1 - (waiting on a burst's tokens + waiting for a request) over the loop's
wall time during the capture. The lower, the more room before the host
sets the pace."""
from benchmark import capture


def read(run):
    loop = capture.loop(run)
    if not loop or not loop.get("wall_s"):
        return None
    return 100.0 * (1.0 - (loop["read_wait_s"] + loop["idle_s"]) / loop["wall_s"])
