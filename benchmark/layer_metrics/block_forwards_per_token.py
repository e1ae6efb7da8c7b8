"""Passes a live lane ran over the tokens the passes committed for a client:
the program's ``block_forwards`` over ``tokens`` (the scheduler's count of
what it credited) over the capture. A block of 4 filled in by two
denoising passes and committed by a third reads 0.75; a commit that rode
in the next block's first pass would read 0.5. None where the program has
no such counter."""
from benchmark import capture


def read(run):
    c = capture.counters(run)
    tokens = c.get("tokens", 0)
    if tokens <= 0 or "block_forwards" not in c:
        return None
    return c["block_forwards"] / tokens
