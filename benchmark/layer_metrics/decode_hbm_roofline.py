"""Bytes a decode step must read, as the configuration's architecture
module counts them (``decode_step_bytes``: for a dense decoder the weights
once, and the keys and values of the requests live during the trace, as the
client's records place them) over the chip's HBM rate, as a share of the
step's device time."""
from benchmark import capture, endtoend, trace

BURST = "jit_fused_burst"
SAMPLES = 40


def read(run):
    seconds, runs = trace.module_seconds(run["trace"] or {}, BURST)
    if not runs:
        return None
    step_s = seconds / (runs * run["steps_per_burst"])
    t0, t1 = run["trace_window"]
    live = [endtoend.live_positions(run["records"], t0 + (t1 - t0) * (i + 0.5) / SAMPLES)[1]
            for i in range(SAMPLES)]
    need = run["architecture"].decode_step_bytes(
        run["config"], sum(live) / SAMPLES, capture.counters(run))
    return 100.0 * need / run["peaks"]["hbm_bytes_per_s"] / step_s
