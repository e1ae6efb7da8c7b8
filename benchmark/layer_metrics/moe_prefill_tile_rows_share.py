"""Rows the prefills' grouped experts gave the matrix unit over the (row,
pick) pairs routed: the program's ``moe_prefill_tile_rows /
moe_prefill_pairs_routed`` over the capture. The grouped kernel
(``ops/experts.py:grouped_swiglu``) cuts the pairs sorted by expert into
row tiles and works a tile once for every group that has a row in it;
``moe_prefill_tile_rows`` is those visits x the tile's rows, counted beside
the call from the group sizes. A pad row's picks and a pick that lands on
another chip's expert join no group, so the share falls with a bucket's
padding and with the share a chip holds, and rises by a tile's edge at
every group; three ``lax.ragged_dot`` over every expert would read 100.
None where the program has no such counter."""
from benchmark import capture


def read(run):
    c = capture.counters(run)
    routed = c.get("moe_prefill_pairs_routed", 0)
    if routed <= 0 or "moe_prefill_tile_rows" not in c:
        return None
    return 100.0 * c["moe_prefill_tile_rows"] / routed
