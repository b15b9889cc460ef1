"""Seeded sample of a window's answers, bounded in memory."""
from __future__ import annotations

import numpy as np


class Reservoir:
    """A uniform sample of up to ``size`` offered items (Algorithm R,
    driven by the run's generator) plus the last item offered."""

    def __init__(self, size: int, rng: np.random.Generator):
        if size < 1:
            raise ValueError(f"sample size must be >= 1, got {size}")
        self.size = size
        self.rng = rng
        self.clear()

    def clear(self) -> None:
        self.sample: dict = {}
        self.seen = 0
        self.last = None

    def offer(self, key, value) -> None:
        self.seen += 1
        self.last = (key, value)
        if len(self.sample) < self.size:
            self.sample[key] = value
            return
        j = int(self.rng.integers(self.seen))
        if j < self.size:
            victim = list(self.sample)[j]
            del self.sample[victim]
            self.sample[key] = value

    def items(self) -> list:
        out = dict(self.sample)
        if self.last is not None:
            out[self.last[0]] = self.last[1]
        return sorted(out.items(), key=lambda kv: kv[0])
