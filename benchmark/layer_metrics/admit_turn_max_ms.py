"""The longest turn of admissions in the window: the largest
``phase_s.admit`` of one poll row. A turn's prefills and inserts are
dispatched from here, so no lane is given a new burst until it ends; a
dispatch that blocks (ROADMAP S5) shows as seconds in one row."""
from benchmark import polls


def read(run):
    return polls.phase_max_ms(run, "admit")
