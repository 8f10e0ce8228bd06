"""Median wall time of the window's ``step()`` calls (host clock), each
ending once its results are on the host."""

import statistics


def read(w):
    if not w.steps:
        return None
    return statistics.median(s.dur for s in w.steps)
