"""Positions of the latent cache the decode steps streamed over the
positions their live lanes held: the program's ``mla_positions_read /
mla_positions_live`` over the capture. The ragged read rounds a lane's
length up to its block of 512, so 100% is a read with no rounding and a
lane of 2.9k positions reads 109%. None where the program has no such
counters."""
from benchmark import capture


def read(run):
    c = capture.counters(run)
    live = c.get("mla_positions_live", 0)
    if live <= 0 or "mla_positions_read" not in c:
        return None
    return 100.0 * c["mla_positions_read"] / live
