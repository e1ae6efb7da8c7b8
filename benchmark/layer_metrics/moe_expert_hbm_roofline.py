"""Bytes of the routed experts the decode steps read (the program's
``moe_experts_touched`` over the capture x one expert's three matrices, as
the architecture module sizes them) over the chip's HBM rate, as a share
of the expert kernel's device time inside ``jit_fused_burst`` over the
same capture. The kernel reads each touched expert once and computes on
it while the next one streams, so its time cannot be under the bytes' at
the peak rate. None without the counters, or where the kernel is not among
the ops the trace's reduction names."""
from benchmark import capture

KERNEL = "jit_fused_burst:touched_experts_ffn"


def read(run):
    c = capture.counters(run)
    touched = c.get("moe_experts_touched", 0)
    seconds = sum(s for name, s in (run["trace"] or {}).get("device_ops", [])
                  if name.startswith(KERNEL))
    if touched <= 0 or seconds <= 0:
        return None
    arch = run["architecture"]
    need = touched * arch.expert_params(run["config"]) * arch.BYTES
    return 100.0 * need / run["peaks"]["hbm_bytes_per_s"] / seconds
