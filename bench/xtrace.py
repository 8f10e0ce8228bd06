"""Reduction of a profiler trace (``.xplane.pb``) to device intervals.

Device work is read from the ``XLA Ops`` line of each ``/device:TPU:<n>``
plane: one event per operation run, named by its HLO instruction, with
its start and duration in nanoseconds. The ``XLA Modules`` line names
the jitted program each ran in (``jit_<function>(<hash>)``). Host spans
are the harness's own ``jax.profiler.TraceAnnotation`` events, named
``bench.<what>``, on the host plane. Both share the profiler's clock.
"""

from __future__ import annotations

import glob
import os
from typing import NamedTuple

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
ANNOTATION_PREFIX = "bench."


class Event(NamedTuple):
    name: str
    start_ns: int
    dur_ns: int

    @property
    def end_ns(self) -> int:
        return self.start_ns + self.dur_ns


class Trace(NamedTuple):
    device_ops: dict[str, list[Event]]    # plane name -> ops, by start
    annotations: list[Event]              # the harness's host spans
    modules: list[Event] = []             # jitted programs, all devices


def find_xplane(log_dir: str) -> str | None:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return found[-1] if found else None


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    device: dict[str, list[Event]] = {}
    notes: list[Event] = []
    modules: list[Event] = []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            lines = {line.name: line for line in plane.lines}
            evs = [Event(e.name, int(e.start_ns), int(e.duration_ns))
                   for e in (lines[OPS_LINE].events
                             if OPS_LINE in lines else [])]
            device[plane.name] = sorted(evs, key=lambda e: e.start_ns)
            if MODULES_LINE in lines:
                modules += [Event(e.name.split("(")[0], int(e.start_ns),
                                  int(e.duration_ns))
                            for e in lines[MODULES_LINE].events]
        elif plane.name == HOST_PLANE:
            notes += [Event(e.name, int(e.start_ns), int(e.duration_ns))
                      for line in plane.lines for e in line.events
                      if e.name.startswith(ANNOTATION_PREFIX)]
    return Trace(device, sorted(notes, key=lambda e: e.start_ns), modules)


def clip(events, lo: int, hi: int) -> list[tuple[int, int]]:
    """Event intervals cut to ``[lo, hi)``, empty ones dropped."""
    out = []
    for e in events:
        a, b = max(e.start_ns, lo), min(e.end_ns, hi)
        if b > a:
            out.append((a, b))
    return out


def union(intervals) -> list[tuple[int, int]]:
    """Merged, sorted intervals covering the same time."""
    merged: list[list[int]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def busy_ns(events, lo: int, hi: int) -> int:
    return sum(b - a for a, b in union(clip(events, lo, hi)))


def gaps(events, lo: int, hi: int) -> list[tuple[int, int]]:
    """Idle intervals of ``[lo, hi)``: no operation ran in them."""
    out, at = [], lo
    for a, b in union(clip(events, lo, hi)):
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if hi > at:
        out.append((at, hi))
    return out


def label_at(notes: list[Event], t: int) -> str:
    """The innermost harness span open at ``t`` (the latest started)."""
    best = None
    for e in notes:
        if e.start_ns > t:
            break
        if e.end_ns > t:
            best = e
    return best.name if best is not None else "outside"
