"""Cache rows the block passes' attention kernel streamed over the rows
their live lanes' lengths held: the program's ``block_rows_read /
block_rows_live`` over the capture. The kernel copies whole blocks of its
walk, so a lane's length is rounded up to the block (the program's own
rule, ``ops.decode_attention.walk_block``, by the bytes a block of 128
keys copies: where that copy is too short to cover the loop's chain the
block is wider, and rounds further): 100% is a read with no rounding. None
where the program has no such counters or no lane was live."""
from benchmark import capture


def read(run):
    c = capture.counters(run)
    live = c.get("block_rows_live", 0)
    if live <= 0 or "block_rows_read" not in c:
        return None
    return 100.0 * c["block_rows_read"] / live
