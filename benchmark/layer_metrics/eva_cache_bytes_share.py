"""What the ring-and-summary cache is of a decode step's bytes: the live
rows of both kinds (the program's counters over the capture's steps) x
16,384 bytes a row and layer, over the architecture module's
``decode_step_bytes`` from those same counters (the rows and the weights
once): whether the mechanism the cell is for still does most of its work.
None where the program has no such counters."""
from benchmark import capture


def read(run):
    arch, cfg, c = run["architecture"], run["config"], capture.counters(run)
    rows = arch.eva_step_rows(cfg, c)
    if rows is None:
        return None
    mine = cfg["num_hidden_layers"] * rows * arch.eva_row_bytes(cfg)
    return 100.0 * mine / arch.decode_step_bytes(cfg, 0.0, c)
