"""Device mesh factory for the snapshot pipelines' mesh steps."""
from __future__ import annotations

import jax


def make_mesh(shape, axes):
    """A mesh of ``shape`` over the available devices, one Auto axis per
    name in ``axes`` (``bench/deploy.py``, ``chip_smoke.py``, tests)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))
