"""Host wall time of the program's ``serve.fetch`` spans (main thread:
the head, then the wait for the step's logits on the host), per image
served."""

SPANS = ("serve.fetch",)


def read(w):
    total = sum(d for name, d in w.spans if name in SPANS)
    if not total or not w.images:
        return None
    return 1000.0 * total / w.images
