"""The program's capture report (``tracing.stop_capture()``), as the child
relayed it with its reply to ``trace_stop``: what the per-layer readers of
the program's own spans and counters share."""


def report(run):
    """The report, or None where the program gave none."""
    snaps = run.get("trace_counters")
    return (snaps[1].get("program") if snaps else None) or None


def loop(run):
    """The scheduler loop's seconds by phase and its counts, over the
    capture; None without a report."""
    return (report(run) or {}).get("loop")


def counters(run):
    """Every numeric entry of the batcher's ``stats``, differenced over
    the capture: what an architecture's cost functions are given (the
    experts a step touched are known only from what was routed). Empty
    without a report."""
    return (report(run) or {}).get("counters") or {}


def requests(run):
    """The request timelines that belong to the run. Where the capture lies
    after the measured window (``--trace 2``) the ring still holds every
    request of the window: those submitted inside it. Where it lies inside
    the window (``--trace 1``): what the ring holds since the window
    opened. Stamps are ``time.monotonic()`` seconds, one clock for the
    parent and the child on one host."""
    rep = report(run)
    if not rep:
        return []
    t_open, t_close = run["window"]
    after = run["trace_window"][0] >= t_close
    return [r for r in rep["requests"]
            if t_open <= r["submit_t"] and (not after or r["submit_t"] < t_close)]
