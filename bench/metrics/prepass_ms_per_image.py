"""Host wall time of the program's ``prepass.schedule`` (tile dependency
tables and Algorithm-1 schedules) and ``pack`` (kernel operands) spans,
per image served. Host time: it includes waits on device results, and
part of it runs on the staging thread while the device works."""

SPANS = ("prepass.schedule", "pack")


def read(w):
    total = sum(d for name, d in w.spans if name in SPANS)
    if not total or not w.images:
        return None
    return 1000.0 * total / w.images
