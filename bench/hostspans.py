"""The program's spans on the profiler's clock, read from the run's trace.

An enabled ``repro.obs`` tracer mirrors each span it records as a
``jax.profiler`` annotation of the same name, so a traced run's
``.xplane.pb`` holds them on the host plane, one line per thread, beside
the device's operations. This module finds that file, keeps the host
events named after the main thread's step spans, and puts the device's
idle time in the window down to what the main thread (the line that
carries ``serve.step``) was doing meanwhile. A program that mirrors nothing
leaves no ``serve.step`` on the host plane, and every reading here is
then ``None``.
"""

from __future__ import annotations

import glob
import os
from typing import NamedTuple

from bench import xtrace

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACE_ROOT = os.path.join(ROOT, ".bench_trace")
STEP_SPAN = "serve.step"
# Main-thread spans that split a step's time, disjoint from one another.
MAIN_SPANS = ("prepass.wait", "exec.segment", "serve.fetch")
KEPT = frozenset(MAIN_SPANS + (STEP_SPAN,))


class HostTrace(NamedTuple):
    window: tuple[int, int] | None        # the bench.window annotation
    device: list[xtrace.Event]            # ops of the first device plane
    lines: list[list[xtrace.Event]]       # host events of each thread


_loaded: dict[str, HostTrace] = {}


def newest_xplane(root: str | None = None) -> str | None:
    pattern = os.path.join(root or TRACE_ROOT, "**", "*.xplane.pb")
    found = glob.glob(pattern, recursive=True)
    return max(found, key=os.path.getmtime) if found else None


def load(path: str) -> HostTrace:
    """One pass over the file, kept for the run (readers share it): the
    window, the first device plane's ops, and the host events named in
    ``KEPT``, by line."""
    if path in _loaded:
        return _loaded[path]
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    window, device, lines = None, None, []
    for plane in pd.planes:
        if plane.name.startswith(xtrace.DEVICE_PLANE_PREFIX):
            if device is None:
                ops = [line for line in plane.lines
                       if line.name == xtrace.OPS_LINE]
                device = sorted(
                    (xtrace.Event(e.name, int(e.start_ns),
                                  int(e.duration_ns))
                     for line in ops for e in line.events),
                    key=lambda e: e.start_ns)
        elif plane.name == xtrace.HOST_PLANE:
            for line in plane.lines:
                evs = []
                for e in line.events:
                    name = e.name
                    if name == "bench.window":
                        window = (int(e.start_ns),
                                  int(e.start_ns) + int(e.duration_ns))
                    elif name in KEPT:
                        evs.append(xtrace.Event(name, int(e.start_ns),
                                                int(e.duration_ns)))
                lines.append(evs)
    _loaded[path] = HostTrace(window, device or [], lines)
    return _loaded[path]


def for_window(w, root: str | None = None) -> HostTrace | None:
    """The run's trace: the newest under ``root``, and only if its
    ``bench.window`` is the window the readers were handed."""
    if w.trace_bounds is None:
        return None
    path = newest_xplane(root)
    if path is None:
        return None
    ht = load(path)
    return ht if ht.window == tuple(w.trace_bounds) else None


def main_thread(ht: HostTrace) -> list[xtrace.Event] | None:
    """The kept events of the line that carries ``serve.step``."""
    for line in ht.lines:
        if any(e.name == STEP_SPAN for e in line):
            return line
    return None


def overlap_ns(a: list[tuple[int, int]], b: list[tuple[int, int]]) -> int:
    """Time two sorted lists of disjoint intervals share."""
    total, i, j = 0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def idle_split(ht: HostTrace, lo: int, hi: int) -> dict[str, float] | None:
    """Share of the device's idle time in ``[lo, hi)`` under each
    main-thread span of ``MAIN_SPANS``, under the rest of ``serve.step``
    (``serve.step.other``) and under no step (``outside``). None without
    the program's spans or without idle time."""
    main = main_thread(ht)
    idle = xtrace.gaps(ht.device, lo, hi)
    total = sum(b - a for a, b in idle)
    if main is None or not ht.device or not total:
        return None
    cover = {name: xtrace.union(xtrace.clip(
        [e for e in main if e.name == name], lo, hi))
        for name in MAIN_SPANS + (STEP_SPAN,)}
    shares = {name: overlap_ns(idle, cover[name]) / total
              for name in MAIN_SPANS}
    in_step = overlap_ns(idle, cover[STEP_SPAN]) / total
    shares["serve.step.other"] = in_step - sum(shares.values())
    shares["outside"] = 1.0 - in_step
    return shares


def idle_share(w, name: str, root: str | None = None) -> float | None:
    """Share of the window's device idle time during which the main
    thread was inside ``name``."""
    ht = for_window(w, root)
    if ht is None:
        return None
    split = idle_split(ht, *w.trace_bounds)
    return None if split is None else split[name]
