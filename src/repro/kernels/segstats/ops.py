"""Public entry point for segstats. The platform picks the form
(``repro.kernels.on_tpu``): the compiled Pallas kernel on a TPU, its
jitted jnp oracle on the CPU, where per-grid-step Pallas interpretation
would dominate the ingest hot path. ``segstats_kernel`` is the kernel
path itself; tests run it in interpret mode."""
from __future__ import annotations

import functools

import jax

from repro.kernels import on_tpu
from repro.kernels.segstats.ref import segstats_ref
from repro.kernels.segstats.segstats import segstats_pallas


@functools.partial(jax.jit, static_argnums=(4, 5),
                   static_argnames=("interpret",))
def segstats_kernel(pids, sids, values, mask, n_principals, n_shards, *,
                    interpret=False):
    return segstats_pallas(pids, sids, values, mask, n_principals, n_shards,
                           interpret=interpret)


_segstats_oracle = jax.jit(segstats_ref, static_argnums=(4, 5))


def segstats(pids, sids, values, mask, n_principals, n_shards=64):
    fn = segstats_kernel if on_tpu() else _segstats_oracle
    return fn(pids, sids, values, mask, n_principals, n_shards)
