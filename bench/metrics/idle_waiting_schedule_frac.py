"""Share of the device's idle time in the traced window during which
the main thread waits for the staging thread's schedules
(``prepass.wait``), on the profiler's clock (``bench/hostspans.py``)."""

from bench import hostspans


def read(w):
    return hostspans.idle_share(w, "prepass.wait")
