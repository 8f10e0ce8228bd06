"""Device time of the fused BLI (+) conv kernel in the window's trace,
per image served."""

from bench.kernels import dcn_fused_batch as kernel


def read(w):
    if w.trace_bounds is None or not w.images:
        return None
    lo, hi = w.trace_bounds
    ns = sum(e.dur_ns for e in w.device_ops()
             if kernel.in_trace(e.name) and lo <= e.start_ns < hi)
    if not ns:
        return None
    return ns / 1e6 / w.images
