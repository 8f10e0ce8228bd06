"""Model FLOPs per image times the images served per second of the
window, over the chip's peak, in %."""

from bench import model


def read(w):
    if not w.window_s or not w.images or not w.peak:
        return None
    return (100.0 * w.images * model.forward_flops(w.net)
            / (w.window_s * w.peak["flops_per_s"]))
