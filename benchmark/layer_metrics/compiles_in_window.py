"""XLA backend compiles that ended inside the measured window: the compile
log's ``serve_events`` (``tracing.CompileLog``, relayed in the capture's
report as ``compiles``) of kind ``backend`` with ``t`` in ``[t_open,
t_close)``. 0 is the only good reading; unlike the engine log's grep it
sees a compile under a second. The ring holds the last 256 events: one
that wrapped inside the window reads at least what it still holds. None
without the log (a program before it)."""
from benchmark import capture


def read(run):
    log = (capture.report(run) or {}).get("compiles")
    if log is None:
        return None
    t_open, t_close = run["window"]
    return sum(e["kind"] == "backend" and t_open <= e["t"] < t_close
               for e in log["serve_events"])
