"""How late the open-loop generator sent: actual send minus due time."""
from benchmark import endtoend


def read(run):
    if run["traffic"]["loop"] != "open":
        return None
    lags = [(r.sent - r.due) * 1e3
            for r in endtoend.due_in(run["records"], *run["window"]) if r.sent]
    return endtoend.percentile(lags, 99) if lags else None
