"""Bytes of the ring and summary rows the decode steps' live lanes held
(the program's ``eva_window_rows_live + eva_summary_rows_live`` over the
capture: summed over the live lanes and the layers, ``(t mod 2048) + 1``
and ``128 floor(t / 2048)`` each, x the 16,384 bytes of K and V of one row
and layer, as the architecture module sizes them) over the chip's HBM
rate, as a share of the EVA decode kernel's device time inside
``jit_fused_burst`` over the same capture. These are the bytes ANY
implementation must read (what the kernel streams past them, a ring's last
block rounded up, is ``eva_rows_read_share``'s), and the kernel computes on
a block while the next one streams, so its time cannot be under the bytes'
at the peak rate: under 100 by construction. None without the counters, or
where the kernel is not among the ops the trace's reduction names."""
from benchmark import capture

KERNEL = "jit_fused_burst:eva_decode_attention"


def read(run):
    c = capture.counters(run)
    rows = c.get("eva_window_rows_live", 0) + c.get("eva_summary_rows_live", 0)
    seconds = sum(s for name, s in (run["trace"] or {}).get("device_ops", [])
                  if name.startswith(KERNEL))
    if rows <= 0 or seconds <= 0:
        return None
    need = rows * run["architecture"].eva_row_bytes(run["config"])
    return 100.0 * need / run["peaks"]["hbm_bytes_per_s"] / seconds
