"""Online tau/k retune, copied from ``benchmarks/hillclimb.py``
(``measure_netsim_online``): after each window, move one coordinate
(tau on even windows, k on odd ones) by a fixed factor in its current
direction, and reverse that coordinate when the window's summed job
throughput fell; values stay inside fixed bounds."""
from __future__ import annotations

import numpy as np

START = {"tau": 0.25, "k": 0.01}
BOUNDS = {"tau": (0.02, 0.8), "k": (1e-4, 0.3)}
FACTOR = {"tau": 1.5, "k": 2.0}
DIRECTION = {"tau": -1, "k": 1}


class Policy:
    def __init__(self):
        self.knobs = dict(START)
        self.direction = dict(DIRECTION)
        self.prev = -np.inf

    def __call__(self, i: int, window_tput: float) -> dict:
        """The action after window ``i``, whose mean throughput summed
        over jobs was ``window_tput`` bytes/s."""
        name = "tau" if i % 2 == 0 else "k"
        if window_tput < self.prev:
            self.direction[name] *= -1
        self.prev = window_tput
        lo, hi = BOUNDS[name]
        self.knobs[name] = float(np.clip(
            self.knobs[name] * FACTOR[name] ** self.direction[name], lo, hi))
        return dict(self.knobs)
