"""The latest a beat of the process's 50 ms heartbeat came over one poll row
of the window: the largest ``host.beat_late_s`` (``tracing.Heartbeat``: a
daemon thread that needs the interpreter lock to note anything). Late by a
stall: the whole process stood (the lock held, frozen, paged out); on time
beside a long ``read_wait_max_ms``: the scheduler thread alone was held. It
is also a wake-up probe of the machine: 1-3 ms on a quiet host, tens where
the host is over its cores (PERF.md section 6, PR 53). None without the rows
or the field."""
from benchmark import polls


def read(run):
    late = [r["host"]["beat_late_s"] for r in polls.in_window(run) or ()
            if "beat_late_s" in r.get("host", ())]
    return 1e3 * max(late) if late else None
