"""The longest the scheduler waited on one poll's bursts in the window: the
largest ``phase_s.read_wait`` of one poll row. About a burst period plus the
longest prefill queued before the burst; seconds where the device or the
runtime held a burst back."""
from benchmark import polls


def read(run):
    return polls.phase_max_ms(run, "read_wait")
