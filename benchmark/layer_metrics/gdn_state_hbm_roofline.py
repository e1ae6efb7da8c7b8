"""Bytes of recurrent state the decode steps moved (the program's
``gdn_lane_steps`` over the capture x one lane's float32 matrix in one
linear layer, as the architecture module sizes it, read once and written
once) over the chip's HBM rate, as a share of the state kernel's device
time inside ``jit_fused_burst`` over the same capture. The kernel copies a
live lane's heads in, updates them in VMEM and copies them out, so its
time cannot be under the bytes' at the peak rate. None without the
counter, or where the kernel is not among the ops the trace's reduction
names."""
from benchmark import capture

KERNEL = "jit_fused_burst:gated_delta_step"


def read(run):
    lane_steps = capture.counters(run).get("gdn_lane_steps", 0)
    seconds = sum(s for name, s in (run["trace"] or {}).get("device_ops", [])
                  if name.startswith(KERNEL))
    if lane_steps <= 0 or seconds <= 0:
        return None
    need = lane_steps * 2 * run["architecture"].gdn_state_bytes(run["config"])
    return 100.0 * need / run["peaks"]["hbm_bytes_per_s"] / seconds
