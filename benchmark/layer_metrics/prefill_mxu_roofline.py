"""FLOPs of the padded prefills run during the trace, as the
configuration's architecture module counts them (``prefill_flops``: from
shapes, counted low where the shapes are not all known) over the chip's
bf16 peak, as a share of the prefill executables' device time."""
from benchmark import capture, trace


def read(run):
    got = trace.prefill_work(run)
    if got is None:
        return None
    seconds, padded, sequences = got
    flops = run["architecture"].prefill_flops(
        run["config"], padded, sequences, capture.counters(run))
    return 100.0 * flops / run["peaks"]["bf16_flops_per_s"] / seconds
