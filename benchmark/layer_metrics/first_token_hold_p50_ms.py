"""How long the token a prefill produced waits for a burst to carry it to
the host: the scheduler's own stamps, first token credited minus the lane
activated (``first_tok_t - decode_start_t``), median over the requests."""
from benchmark import capture, endtoend


def read(run):
    held = [(r["first_tok_t"] - r["decode_start_t"]) * 1e3
            for r in capture.requests(run)
            if r["first_tok_t"] and r["decode_start_t"]]
    return endtoend.percentile(held, 50) if held else None
