"""What the entry point and the fronts add to time to first token: the
client's median (first token received - sent) minus the scheduler's own
median submit-to-first-token time (``gen_ttft_ms``' samples, which include
its queue wait) over the requests of the window."""
from benchmark import endtoend


def read(run):
    client = [(r.first - r.sent) * 1e3
              for r in endtoend.due_in(run["records"], *run["window"]) if r.first]
    server = [ttft * 1e3 for _q, ttft, _t in run["slo"]]
    if not client or not server:
        return None
    return endtoend.percentile(client, 50) - endtoend.percentile(server, 50)
