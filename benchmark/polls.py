"""The scheduler's poll rows of the measured window: the flight recorder's
ring as the program's capture report carries it (``report["polls"]``), which
the per-layer readers of the loop's clock share. A ``poll`` row is a span of
the scheduler thread's time: ``t`` (monotonic, the parent's clock too) is
where its stretch began and ``phase_s`` its seconds by phase from there
(``seldon_core_tpu/serving/continuous.py:_loop``)."""
import sys

from benchmark import capture


def in_window(run):
    """The ``poll`` rows whose stretch began in ``[t_open, t_close)``,
    oldest first. None where the report carries no rows (a program without
    them, a ring set to 0, no report), and, with a line on stderr, where the
    ring wrapped inside the window: its oldest row is not its first and
    lies past ``t_open``, so rows of the window are gone. Where the ring was
    read before the window closed (``--trace 1``: the capture lies inside
    it), the rows are those of ``[t_open, t1)`` only, and a line on stderr
    says how much of the window that is."""
    rep = capture.report(run) or {}
    rows = rep.get("polls")
    if not rows:
        return None
    t_open, t_close = run["window"]
    if rows[0].get("seq", 0) > 0 and rows[0]["t"] >= t_open:
        print(f"benchmark: the flight recorder wrapped inside the window: its "
              f"oldest row lies {rows[0]['t'] - t_open:.1f}s past the opening",
              file=sys.stderr)
        return None
    if rep["t1"] < t_close:
        print(f"benchmark: the poll rows were read {t_close - rep['t1']:.1f}s "
              f"before the window closed: they cover its first "
              f"{rep['t1'] - t_open:.1f}s of {t_close - t_open:.1f}s",
              file=sys.stderr)
    return [r for r in rows
            if r.get("type") == "poll" and t_open <= r["t"] < t_close]


def phase_max_ms(run, phase):
    """The most one row of the window spent in ``phase``, in ms."""
    rows = in_window(run)
    if not rows:
        return None
    return 1e3 * max(r["phase_s"].get(phase, 0.0) for r in rows)
