"""Host wall time of the program's ``prepass.tdt`` spans (each image's
tile dependency tables; staging thread), per image served. A leaf span,
so its time is its own; it includes the wait for the stage-1
coordinates it reads."""

SPANS = ("prepass.tdt",)


def read(w):
    total = sum(d for name, d in w.spans if name in SPANS)
    if not total or not w.images:
        return None
    return 1000.0 * total / w.images
