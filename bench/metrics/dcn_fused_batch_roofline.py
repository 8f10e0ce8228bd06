"""Least time the chip could take for the fused kernel's calls in the
window, over their measured device time, in %. Each call's least time is
the larger of its FLOPs over the peak and its bytes over the bandwidth
(``bench/kernels/dcn_fused_batch.py``)."""

import sys

from bench.kernels import dcn_fused_batch as kernel


def read(w):
    if w.trace_bounds is None or not w.peak:
        return None
    lo, hi = w.trace_bounds
    evs = [e for e in w.device_ops()
           if kernel.in_trace(e.name) and lo <= e.start_ns < hi]
    measured = sum(e.dur_ns for e in evs) / 1e9
    if not measured:
        return None
    calls = kernel.calls(w.layers, [s.width for s in w.steps])
    by_flops = sum(f / w.peak["flops_per_s"] for f, _ in calls)
    by_bytes = sum(b / w.peak["hbm_bytes_per_s"] for _, b in calls)
    least = sum(max(f / w.peak["flops_per_s"], b / w.peak["hbm_bytes_per_s"])
                for f, b in calls)
    print(f"bench: dcn_fused_batch: {len(evs)} kernel events for "
          f"{len(calls)} calls; {measured!r} s measured; least "
          f"{least!r} s (compute {by_flops!r} s, memory {by_bytes!r} s: "
          f"{'compute' if by_flops >= by_bytes else 'memory'} bound)",
          file=sys.stderr)
    return 100.0 * least / measured
