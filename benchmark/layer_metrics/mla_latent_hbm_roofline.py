"""Bytes of latent rows the decode steps streamed (the program's
``mla_positions_read`` over the capture: summed over the live lanes and the
latent layers, ``512 x ceil(len / 512)`` each, x the 1,152 bytes of a
position and layer that hold something, as the architecture module sizes
them) over the chip's HBM rate, as a share of the latent kernel's device
time inside ``jit_fused_burst`` over the same capture. The kernel copies
each of those positions once (the row's 640 lanes: 1,280 bytes, so the
share cannot pass 90%) and computes on a block while the next one
streams, so its time cannot be under the bytes' at the peak rate. None
without the counter, or where the kernel is not among the ops the trace's
reduction names."""
from benchmark import capture

KERNEL = "jit_fused_burst:latent_decode_attention"


def read(run):
    positions = capture.counters(run).get("mla_positions_read", 0)
    seconds = sum(s for name, s in (run["trace"] or {}).get("device_ops", [])
                  if name.startswith(KERNEL))
    if positions <= 0 or seconds <= 0:
        return None
    need = positions * run["architecture"].latent_bytes_per_position(run["config"])
    return 100.0 * need / run["peaks"]["hbm_bytes_per_s"] / seconds
