"""Share of the device's idle time in the traced window during which
the main thread enqueues the graph's programs (``exec.segment``), on the
profiler's clock (``bench/hostspans.py``). Logs the whole split of the
idle time by main-thread span."""

import sys

from bench import hostspans


def read(w):
    ht = hostspans.for_window(w)
    split = None if ht is None else hostspans.idle_split(ht,
                                                         *w.trace_bounds)
    if split is None:
        return None
    print("bench: device idle time by main-thread span: "
          + ", ".join(f"{k} {v!r}" for k, v in split.items()),
          file=sys.stderr)
    return split["exec.segment"]
