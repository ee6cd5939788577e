"""Public entry point for the grouped DDSketch update, signature-
compatible with sketches.ddsketch.update_grouped, plus a leading stream
axis: ``pids`` and ``mask`` may be (S, N), S principal streams over the
same (N,) values, applied as S grouped updates. The platform picks the
form (``repro.kernels.on_tpu``): the compiled Pallas kernel on a TPU,
one call for all S streams; the production jnp update (the kernel's
reference) on the CPU, once per stream, where per-grid-step Pallas
interpretation would dominate the ingest and snapshot hot paths.
``kernel_update_grouped`` is the kernel path itself; tests run it in
interpret mode. The kernel takes a 0/1 ``mask`` (see its module)."""
from __future__ import annotations

import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.core.sketches import ddsketch as dds
from repro.core.sketches.ddsketch import DDSketchConfig
from repro.kernels import on_tpu
from repro.kernels.ddsketch.ddsketch import grouped_update_pallas


@functools.partial(jax.jit, static_argnums=(0, 4),
                   static_argnames=("interpret",))
def kernel_update_grouped(cfg: DDSketchConfig, state: Dict,
                          values: jax.Array, pids: jax.Array,
                          n_principals: int, mask: jax.Array, *,
                          interpret: bool = False) -> Dict:
    delta = grouped_update_pallas(cfg, values, pids, mask, n_principals,
                                  interpret=interpret)
    return dds.merge(state, delta)


def update_grouped(cfg: DDSketchConfig, state: Dict, values: jax.Array,
                   pids: jax.Array, n_principals: int,
                   mask: Optional[jax.Array] = None) -> Dict:
    if mask is None:
        mask = jnp.ones(pids.shape, jnp.float32)
    if on_tpu():
        return kernel_update_grouped(cfg, state, values, pids,
                                     n_principals, mask)
    n = values.shape[0]
    for pid, m in zip(pids.reshape(-1, n), mask.reshape(-1, n)):
        state = dds.update_grouped(cfg, state, values, pid, n_principals,
                                   mask=m)
    return state
