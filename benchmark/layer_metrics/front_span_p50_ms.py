"""What the front adds to time to first token, stamped in the front:
(route entered to submitted) + (first token credited to its chunk handed
to the connection), median over the requests."""
from benchmark import capture, endtoend


def read(run):
    spans = [((r["submit_t"] - r["received_t"])
              + (r["first_write_t"] - r["first_tok_t"])) * 1e3
             for r in capture.requests(run)
             if r["received_t"] and r["first_write_t"] and r["first_tok_t"]]
    return endtoend.percentile(spans, 50) if spans else None
