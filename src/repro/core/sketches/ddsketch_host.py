"""Host (numpy) DDSketch with the same bucket layout as the device sketch —
used by the Table VII accuracy tests and as the oracle for the Pallas
kernel tests."""
from __future__ import annotations

import math

import numpy as np

from repro.core.sketches.base import SketchBase
from repro.core.sketches.ddsketch import DDSketchConfig


class DDSketch(SketchBase):
    name = "DDSketch"

    def __init__(self, alpha: float = 0.01, n_buckets: int = 2048,
                 offset: int = 128):
        self.cfg = DDSketchConfig(alpha, n_buckets, offset)
        self.counts = np.zeros(n_buckets, np.float64)
        self.zero_count = 0.0
        self.n = 0
        self.total = 0.0
        self.min = math.inf
        self.max = -math.inf

    def update(self, values) -> None:
        v = np.asarray(values, np.float64).ravel()
        if v.size == 0:
            return
        c = self.cfg
        safe = np.maximum(v, c.min_value)
        idx = np.ceil(np.log(safe) / math.log(c.gamma)).astype(np.int64) + c.offset
        idx = np.clip(idx, 0, c.n_buckets - 1)
        zero = v <= c.min_value
        self.zero_count += float(zero.sum())
        np.add.at(self.counts, idx[~zero], 1.0)
        self.n += v.size
        self.total += float(v.sum())
        self.min = min(self.min, float(v.min()))
        self.max = max(self.max, float(v.max()))

    def merge(self, other: "DDSketch") -> None:
        assert self.cfg == other.cfg
        self.counts += other.counts
        self.zero_count += other.zero_count
        self.n += other.n
        self.total += other.total
        self.min = min(self.min, other.min)
        self.max = max(self.max, other.max)

    def quantile(self, q: float) -> float:
        if self.n == 0:
            return float("nan")
        rank = q * (self.n - 1)
        if rank < self.zero_count:
            return 0.0
        cum = self.zero_count + np.cumsum(self.counts)
        idx = int(np.searchsorted(cum, rank, side="right"))
        idx = min(idx, self.cfg.n_buckets - 1)
        g = self.cfg.gamma
        val = 2.0 * g ** (idx - self.cfg.offset) / (g + 1.0)
        return float(min(max(val, 0.0), self.max))
