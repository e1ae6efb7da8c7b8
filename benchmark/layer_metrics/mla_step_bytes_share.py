"""What latent attention moves of a decode step's bytes: the live lanes'
latent rows (``mla_positions_live`` over the capture x 1,152 bytes) and
the attention's weights, over the architecture module's
``decode_step_bytes`` at those same positions (``mla_step_bytes``: both
from the program's counters alone). Whether the mechanism the cell is for
still does most of its work. None where the program has no such
counters."""
from benchmark import capture


def read(run):
    parts = run["architecture"].mla_step_bytes(
        run["config"], capture.counters(run))
    if parts is None:
        return None
    mine, step = parts
    return 100.0 * mine / step
