"""Bytes of the cache rows the block passes' attention kernel read (the
program's ``block_rows_read`` over the capture: a live lane's length
rounded up to the kernel's block, over passes and layers, x one position's
keys and values in one layer, as the architecture module's
``block_attn_bytes`` sizes them) over the chip's HBM rate, as a share of
the ``block_decode_attention`` kernel's device time inside
``jit_fused_burst`` over the same capture. The kernel computes on a block
while the next one streams, so its time cannot be under the bytes' at the
peak rate. None without the counter, or where the kernel is not among the
ops the trace's reduction names."""
from benchmark import capture

KERNEL = "block_decode_attention"


def read(run):
    arch = run["architecture"]
    if not hasattr(arch, "block_attn_bytes"):
        return None
    need = arch.block_attn_bytes(run["config"], capture.counters(run))
    seconds = sum(s for name, s in (run["trace"] or {}).get("device_ops", [])
                  if name.startswith("jit_fused_burst")
                  and name.split(":", 1)[-1].startswith(KERNEL))
    if not need or seconds <= 0:
        return None
    return 100.0 * need / run["peaks"]["hbm_bytes_per_s"] / seconds
