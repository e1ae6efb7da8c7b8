"""Summary rows the decode steps wrote from inside the EVA decode kernel
over the summary rows they wrote at all: the program's
``eva_summaries_written_in_kernel / eva_summaries_written`` over the
capture. A step that completes a chunk of 16 positions pools it into one
row of the lane's summaries a layer; ``eva_summaries_written`` counts those
(lane, layer) rows from the lanes' positions, and the batcher adds them to
``eva_summaries_written_in_kernel`` where the burst's kernel lands them
itself (the model says so for the platform the burst is lowered for), not
where the model's step scatters them. 100 or 0 on one program; None where
the program has no such counter or the capture's steps completed no
chunk."""
from benchmark import capture


def read(run):
    c = capture.counters(run)
    written = c.get("eva_summaries_written", 0)
    if written <= 0 or "eva_summaries_written_in_kernel" not in c:
        return None
    return 100.0 * c["eva_summaries_written_in_kernel"] / written
