"""Bytes of recurrent state the decode steps moved (the program's
``ssm_lane_steps`` over the capture x one lane's float32 state in one Mamba
layer, as the architecture module's ``ssm_kernel_state_bytes`` sizes it,
read once and written once) over the chip's HBM rate, as a share of the
``selective_scan_step`` kernel's device time inside ``jit_fused_burst``
over the same capture. The kernel copies a live lane's state in, updates it
in VMEM and copies it out, so its time cannot be under the bytes' at the
peak rate (the lane's tail, a tenth as many bytes, goes through the
convolution's own ops beside the kernel: neither its bytes nor their time
are in this share). None without the counter, or where the trace names no
such kernel."""
from benchmark import capture

BURST = ("jit_fused_burst",)
KERNEL = "selective_scan_step"


def read(run):
    arch = run["architecture"]
    lane_steps = capture.counters(run).get("ssm_lane_steps", 0)
    if lane_steps <= 0 or not hasattr(arch, "ssm_kernel_state_bytes"):
        return None
    seconds = arch.kernel_seconds(run, BURST, KERNEL)
    if not seconds:
        return None
    need = lane_steps * 2 * arch.ssm_kernel_state_bytes(run["config"])
    return 100.0 * need / run["peaks"]["hbm_bytes_per_s"] / seconds
