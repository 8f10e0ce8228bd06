"""Share of the traced window in which no operation ran on the device:
1 - (union of the device's operation intervals / window)."""

from bench import xtrace


def read(w):
    if w.trace_bounds is None or w.trace is None:
        return None
    lo, hi = w.trace_bounds
    busy = [xtrace.busy_ns(evs, lo, hi)
            for evs in w.trace.device_ops.values()]
    if not busy or not any(busy):
        return None
    return 1.0 - sum(busy) / len(busy) / (hi - lo)
