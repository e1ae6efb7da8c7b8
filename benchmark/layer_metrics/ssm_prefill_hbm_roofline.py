"""Bytes the prefill scan must stream for the steps it walked (the
program's ``ssm_prefill_steps_walked`` over the capture, a (sequence, Mamba
layer, position) each, x that step's operands: ``c`` and ``delta`` in and
``y`` out, 5,120 bfloat16 each, and ``B`` and ``C`` of 16, as the
architecture module's ``ssm_prefill_bytes`` sizes them) over the chip's HBM
rate, as a share of the ``selective_scan_prefill`` kernel's device time
inside the prefill executables over the same capture. The kernel is bound
by the vector unit, not by HBM (a step is 82 k numbers of state through a
decay, a product and two sums, against 31 KB streamed), and the table of
peaks has no vector-unit peak: the share reads low, and says how far the
scan is from the one bound the benchmark can state. None without the
counter, or where the trace names no such kernel."""
from benchmark import capture

PREFILL = ("jit_prefill_one", "jit_prefill_many")
KERNEL = "selective_scan_prefill"


def read(run):
    arch = run["architecture"]
    if not hasattr(arch, "ssm_prefill_bytes"):
        return None
    need = arch.ssm_prefill_bytes(run["config"], capture.counters(run))
    if not need:
        return None
    seconds = arch.kernel_seconds(run, PREFILL, KERNEL)
    if not seconds:
        return None
    return 100.0 * need / run["peaks"]["hbm_bytes_per_s"] / seconds
