"""FLOPs of the padded prefills run during the trace (from shapes, counted
low where the shapes are not all known) over the chip's bf16 peak, as a
share of the prefill executables' device time."""
from benchmark import costs, trace


def read(run):
    got = trace.prefill_work(run)
    if got is None:
        return None
    seconds, padded, sequences = got
    flops = costs.prefill_flops(run["config"], padded, sequences)
    return 100.0 * flops / run["peaks"]["bf16_flops_per_s"] / seconds
