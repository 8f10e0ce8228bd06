"""The one traffic generator: reads a mix's data file, draws from a seed.

A mix is ``bench/traffic/<name>.json``:

    {"arrivals": "poisson", "rate_per_s": 1.0, "order_seed": 1}
        Open loop: single-image requests due at Poisson times. The gaps
        between arrivals are the same multiset for every seed (quantiles
        of the exponential distribution, scaled to fill the window),
        put in an order drawn from ``order_seed``: every run's seed sees
        the same arrival times and draws only its images. Where the run's
        seed ordered the gaps, the order alone moved the median latency
        of a 24-request window by up to 30 %.
    {"arrivals": "backlog", "queued_per_slot": 2}
        Closed loop: the queue is topped up to ``queued_per_slot * slots``
        single-image requests before every step, so every step is full.

and, for either, the frames:

    "streams": 0             every frame is a new image;
    "streams": S, "repeat_share": r
                             frames come from S fixed cameras in turn;
                             in every round of S frames, round(r * S)
                             streams (chosen by the seed) re-send their
                             previous frame unchanged and the rest send a
                             new one. The first round is all new.

Images are N(0, 1) float32 planes drawn from ``(seed, image id)``, so
the same seed gives the same inputs.
"""

from __future__ import annotations

import json
import math

import numpy as np


class Mix:
    def __init__(self, spec: dict, seed: int, shape: tuple[int, ...]):
        self.spec = spec
        self.arrivals = spec["arrivals"]
        if self.arrivals not in ("poisson", "backlog"):
            raise ValueError(f"unknown arrivals {self.arrivals!r}")
        self.seed = int(seed)
        self.shape = tuple(shape)
        self.streams = int(spec.get("streams", 0))
        self.repeat_share = float(spec.get("repeat_share", 0.0))
        self._rng = np.random.default_rng([self.seed, 0])
        self._frame = 0
        self._image_of_stream: list[int | None] = [None] * self.streams
        self._repeats: set[int] = set()
        self._next_image = 0

    @classmethod
    def from_file(cls, path: str, seed: int, shape) -> "Mix":
        with open(path) as f:
            return cls(json.load(f), seed, shape)

    # -- images -------------------------------------------------------------

    def image(self, image_id: int) -> np.ndarray:
        rng = np.random.default_rng([self.seed, 1, image_id])
        return rng.standard_normal(self.shape, dtype=np.float32)

    def warmup_image(self, k: int) -> np.ndarray:
        """Images for warming up, never among the traffic's frames."""
        rng = np.random.default_rng([self.seed, 2, k])
        return rng.standard_normal(self.shape, dtype=np.float32)

    def next_frame(self) -> int:
        """Image id of the next frame in submit order."""
        f = self._frame
        self._frame += 1
        if self.streams == 0:
            return self._new_image()
        s = f % self.streams
        if s == 0:
            n_rep = round(self.repeat_share * self.streams)
            picks = self._rng.permutation(self.streams)[:n_rep]
            self._repeats = set(int(p) for p in picks)
        prev = self._image_of_stream[s]
        if prev is None or s not in self._repeats:
            self._image_of_stream[s] = self._new_image()
        return self._image_of_stream[s]

    def _new_image(self) -> int:
        self._next_image += 1
        return self._next_image - 1

    # -- arrivals -----------------------------------------------------------

    def due_times(self, seconds: float) -> np.ndarray:
        """Open loop: offsets (s) from the window's start at which each
        request is due, all inside ``[0, seconds)``."""
        rate = float(self.spec["rate_per_s"])
        n = max(1, round(rate * seconds))
        u = (np.arange(n) + 0.5) / n
        gaps = -np.log1p(-u)
        gaps *= seconds / gaps.sum()
        order = np.random.default_rng([int(self.spec["order_seed"]), 3])
        gaps = order.permutation(gaps)
        return np.concatenate([[0.0], np.cumsum(gaps[:-1])])

    def queue_target(self, slots: int) -> int:
        """Closed loop: images kept queued ahead of every step."""
        return int(math.ceil(float(self.spec["queued_per_slot"]) * slots))

    def warmup_steps(self, slots: int) -> list[tuple[int, bool]]:
        """(width, from the traffic?) of each warm-up step: every width up
        to ``slots`` an open loop can drive, of images outside the
        traffic; for a backlog, full steps of the traffic's own first
        frames, two rounds where streams repeat so that the window's
        first step already finds the repeats' schedules cached."""
        if self.arrivals == "poisson":
            return [(w, False) for w in range(1, slots + 1)]
        return [(slots, True)] * (2 if self.streams else 1)
