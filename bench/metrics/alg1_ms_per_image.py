"""Host wall time of the program's ``prepass.alg1`` spans (staging
thread, one per schedule-cache miss: the composite tile dependency
table of the group's layers, Algorithm 1 over it and its schedule
arrays), per image served. A leaf nested in ``prepass.schedule``, so
part of ``prepass_ms_per_image`` too. A program without the span reads
None."""

SPANS = ("prepass.alg1",)


def read(w):
    total = sum(d for name, d in w.spans if name in SPANS)
    if not total or not w.images:
        return None
    return 1000.0 * total / w.images
