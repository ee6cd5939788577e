"""Production mesh factories.

``make_production_mesh`` is a FUNCTION (importing this module never touches
jax device state). Single pod: (16, 16) = 256 chips, axes (data, model).
Multi-pod: (2, 16, 16) = 512 chips with a leading "pod" axis that carries
pure data parallelism across the pod-interconnect.
"""
from __future__ import annotations

import jax


def _make_mesh(shape, axes):
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_mesh(shape, axes):
    """Arbitrary mesh (elastic restarts, tests, hillclimb variants)."""
    return _make_mesh(shape, axes)


def make_smoke_mesh(n_data: int = 1, n_model: int = 1):
    """Tiny mesh over however many (host) devices are available."""
    return make_mesh((n_data, n_model), ("data", "model"))


# Hardware constants for the roofline model: TPU v5e.
PEAK_FLOPS_BF16 = 197e12      # per chip
HBM_BW = 819e9                # bytes/s per chip
ICI_BW = 50e9                 # bytes/s per link (~per chip, one direction)
HBM_PER_CHIP = 16 * 2**30     # 16 GiB
