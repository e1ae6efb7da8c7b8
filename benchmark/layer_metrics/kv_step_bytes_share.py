"""What the attention layers' cache moves of a decode step's bytes: the
live lanes' keys and values (``kv_rows_live`` over the capture: the lanes'
lengths summed over the attention layers, x one position's keys and values
in one layer) over the architecture module's ``decode_step_bytes`` at those
same positions (``kv_step_bytes``: both from the program's counters alone).
How much of the step is the attention this cell was sized for. None where
the program has no such counters."""
from benchmark import capture


def read(run):
    arch = run["architecture"]
    if not hasattr(arch, "kv_step_bytes"):
        return None
    parts = arch.kv_step_bytes(run["config"], capture.counters(run))
    if parts is None:
        return None
    mine, step = parts
    return 100.0 * mine / step
