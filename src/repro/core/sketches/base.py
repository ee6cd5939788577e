"""Uniform OO API over the four quantile sketches (Table VII).

DDSketch is the production (device-native) sketch; the other three are
mergeable host implementations used by the Table VII accuracy tests,
mirroring the paper's evaluation of Datadog / Apache DataSketches
implementations with default error parameters.
"""
from __future__ import annotations

from typing import Sequence

import numpy as np


class SketchBase:
    name = "base"

    def update(self, values: np.ndarray) -> None:
        raise NotImplementedError

    def merge(self, other: "SketchBase") -> None:
        raise NotImplementedError

    def quantile(self, q: float) -> float:
        raise NotImplementedError

    def quantiles(self, qs: Sequence[float]) -> np.ndarray:
        return np.array([self.quantile(q) for q in qs])
