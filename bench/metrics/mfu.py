"""Model FLOPs per image times the images served per second of the
window, over the peak of the chips the cell uses, in %."""


def read(w):
    if not w.window_s or not w.images or not w.peak:
        return None
    return (100.0 * w.images * w.flops_per_image
            / (w.window_s * w.chips * w.peak["flops_per_s"]))
