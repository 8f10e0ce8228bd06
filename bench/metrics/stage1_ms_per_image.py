"""Host wall time of the program's ``prepass.stage1`` spans (the stage-1
chain of each fused group: offset convs, sampling coordinates, the dense
plane's advance; staging thread), per image served. Host time: the
enqueue of those programs, plus any wait for their results."""

SPANS = ("prepass.stage1",)


def read(w):
    total = sum(d for name, d in w.spans if name in SPANS)
    if not total or not w.images:
        return None
    return 1000.0 * total / w.images
