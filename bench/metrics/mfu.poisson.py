"""Model FLOPs of the images the open loop served, over the summed wall
time of its steps times the chip's peak, in %. Idle time between
arrivals is left out: it is the share of the peak while serving."""

from bench import model


def read(w):
    busy = sum(s.dur for s in w.steps)
    if not busy or not w.peak:
        return None
    served = sum(s.width for s in w.steps)
    return (100.0 * served * model.forward_flops(w.net)
            / (busy * w.peak["flops_per_s"]))
