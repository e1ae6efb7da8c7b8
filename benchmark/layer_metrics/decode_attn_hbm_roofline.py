"""Bytes of keys and values the one-position decode attention kernel
streamed (the program's ``kv_rows_read`` over the capture: summed over the
live lanes, the attention layers and the steps, each lane's length rounded
up to the kernel's block, x one position's keys and values in one layer, as
the architecture module's ``decode_attn_bytes`` sizes them) over the chip's
HBM rate, as a share of the ``ragged_decode_attention`` kernel's device
time inside ``jit_fused_burst`` over the same capture. The kernel copies
each of those positions once and computes on a block while the next one
streams, so its time cannot be under the bytes' at the peak rate. The
seconds are every event of the kernel in the burst, from the run's own
events (the architecture module's ``kernel_seconds``, as
``ssm_state_hbm_roofline`` and ``swa_ring_hbm_roofline`` read theirs): a
kernel made faster may leave the ten ops the reduction names and must not
fall silent for it. None without the counter (a program whose step counts no such rows, or takes
the dots), or where the trace names no such kernel."""
from benchmark import capture

BURST = ("jit_fused_burst",)
KERNEL = "ragged_decode_attention"


def read(run):
    arch = run["architecture"]
    if not hasattr(arch, "decode_attn_bytes"):
        return None
    need = arch.decode_attn_bytes(run["config"], capture.counters(run))
    if not need:
        return None
    seconds = arch.kernel_seconds(run, BURST, KERNEL)
    if not seconds:
        return None
    return 100.0 * need / run["peaks"]["hbm_bytes_per_s"] / seconds
