"""The band's operations in the window layers' prefill (a query's scores
over keys of 192 and its product over values of 128, over the ``min(i + 1,
128)`` keys it sees, 64 heads, as the architecture module's
``swa_band_flops`` counts them from the padded tokens and sequences the
prefills of the capture took) over the matrix unit's bf16 peak, as a share
of the ``swa_prefill_attention`` kernel's device time inside the prefill
executables over the same capture. The kernel walks key tiles of 256 or 512
under 256 query rows, so most of each tile it multiplies lies outside a
band of 128: the share reads low by construction, and says what a tile cut
to the band could recover. None where no prefill ran in the capture, or
where the trace names no such kernel."""
from benchmark import trace

PREFILL = ("jit_prefill_one", "jit_prefill_many")
KERNEL = "swa_prefill_attention"


def read(run):
    arch = run["architecture"]
    got = trace.prefill_work(run)
    if got is None or not hasattr(arch, "swa_band_flops"):
        return None
    _seconds, padded, sequences = got
    seconds = arch.kernel_seconds(run, PREFILL, KERNEL)
    if not seconds:
        return None
    flops = arch.swa_band_flops(run["config"], padded, sequences)
    return 100.0 * flops / run["peaks"]["bf16_flops_per_s"] / seconds
