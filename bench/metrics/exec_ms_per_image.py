"""Host wall time of the program's ``exec.segment`` spans (main thread,
one per segment of the graph: the enqueue of its programs, which run on
the device asynchronously), per image served."""

SPANS = ("exec.segment",)


def read(w):
    total = sum(d for name, d in w.spans if name in SPANS)
    if not total or not w.images:
        return None
    return 1000.0 * total / w.images
