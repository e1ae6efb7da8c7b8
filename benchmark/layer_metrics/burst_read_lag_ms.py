"""Dispatch of a decode burst to the host reading its tokens, the mean
over the bursts read during the capture: the pipeline's depth in time."""
from benchmark import capture


def read(run):
    loop = capture.loop(run)
    if not loop or not loop.get("bursts"):
        return None
    return 1e3 * loop["burst_read_lag_s_sum"] / loop["bursts"]
