"""What the Mamba layers' per-lane memory moves of a decode step's bytes:
the live lanes' state and tails, read once and written once
(``ssm_lane_steps`` over the capture x ``ssm_state_bytes``), over the
architecture module's ``decode_step_bytes`` at those same counters, the
keys and values at the lanes' own lengths (``ssm_step_share``: both from the
program's counters alone). How much of the step is the state this cell was
sized for. None where the program has no such counters."""
from benchmark import capture


def read(run):
    arch = run["architecture"]
    if not hasattr(arch, "ssm_step_share"):
        return None
    parts = arch.ssm_step_share(run["config"], capture.counters(run))
    if parts is None or not parts[1]:
        return None
    mine, step = parts
    return 100.0 * mine / step
