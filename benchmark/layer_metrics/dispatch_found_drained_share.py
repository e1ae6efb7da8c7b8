"""How often the host came to a device with nothing left to do: the poll
rows of the window with ``drained`` (sampled before the poll's first
dispatch: the newest array the loop had dispatched was ready) over the
window's poll rows. The device then idles until that dispatch lands."""
from benchmark import polls


def read(run):
    rows = polls.in_window(run)
    if not rows:
        return None
    return 100.0 * sum(1 for r in rows if r.get("drained")) / len(rows)
