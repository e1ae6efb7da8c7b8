"""Bytes of keys and values the one-position decode attention kernel
streamed (the program's ``kv_rows_read`` over the capture: summed over the
live lanes, the attention layers and the steps, each lane's length rounded
up to the kernel's block, x one position's keys and values in one layer, as
the architecture module's ``decode_attn_bytes`` sizes them) over the chip's
HBM rate, as a share of the ``ragged_decode_attention`` kernel's device
time inside ``jit_fused_burst`` over the same capture. The kernel copies
each of those positions once and computes on a block while the next one
streams, so its time cannot be under the bytes' at the peak rate. None
without the counter (a program whose step counts no such rows, or takes
the dots), or where the kernel is not among the ops the trace's reduction
names."""
from benchmark import capture

KERNEL = "jit_fused_burst:ragged_decode_attention"


def read(run):
    arch = run["architecture"]
    if not hasattr(arch, "decode_attn_bytes"):
        return None
    need = arch.decode_attn_bytes(run["config"], capture.counters(run))
    seconds = sum(s for name, s in (run["trace"] or {}).get("device_ops", [])
                  if name.startswith(KERNEL))
    if not need or seconds <= 0:
        return None
    return 100.0 * need / run["peaks"]["hbm_bytes_per_s"] / seconds
