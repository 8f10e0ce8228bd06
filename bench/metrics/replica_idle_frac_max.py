"""Largest share of the traced window in which no operation ran on one
device plane: max over planes of 1 - (union of that plane's operation
intervals / window). ``device_idle_frac`` reads the planes' mean, so the
gap between the two is the replicas' imbalance."""

from bench import xtrace


def read(w):
    if w.trace_bounds is None or w.trace is None:
        return None
    lo, hi = w.trace_bounds
    busy = [xtrace.busy_ns(evs, lo, hi)
            for evs in w.trace.device_ops.values()]
    if not busy or not any(busy):
        return None
    return 1.0 - min(busy) / (hi - lo)
