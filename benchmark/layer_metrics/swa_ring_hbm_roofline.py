"""Bytes of the window layers' rings the ring read streamed (the program's
``kv_positions_read_window`` over the capture: summed over the live lanes,
the window layers and the steps, a ring's one block of 128 rows, x one
row's keys of 192 and values of 128 over 8 KV heads, as the architecture
module's ``ring_bytes`` sizes what holds something) over the chip's HBM
rate, as a share of the ``swa_ring_attention`` kernel's device time inside
``jit_fused_burst`` over the same capture. The kernel copies a live lane's
ring once and computes on it while the next lane's streams, so its time
cannot be under the bytes' at the peak rate; a walk of ONE block a lane
has little to hide its chain behind, and the share says how far that
leaves it. None without the counter, or where the trace names no such
kernel."""
from benchmark import capture

BURST = ("jit_fused_burst",)
KERNEL = "swa_ring_attention"


def read(run):
    arch = run["architecture"]
    if not hasattr(arch, "ring_bytes"):
        return None
    need = arch.ring_bytes(run["config"], capture.counters(run))
    if not need:
        return None
    seconds = arch.kernel_seconds(run, BURST, KERNEL)
    if not seconds:
        return None
    return 100.0 * need / run["peaks"]["hbm_bytes_per_s"] / seconds
