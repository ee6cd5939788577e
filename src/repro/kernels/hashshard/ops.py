"""jit'd wrappers for the hashshard kernel."""
from __future__ import annotations

import functools

import jax

from repro.kernels import on_tpu
from repro.kernels.hashshard.hashshard import hashshard_pallas
from repro.kernels.hashshard.ref import hashshard_ref


@functools.partial(jax.jit, static_argnums=(2,))
def _hashshard_compiled(byte_rows: jax.Array, lengths: jax.Array,
                        n_shards: int):
    return hashshard_pallas(byte_rows, lengths, n_shards, interpret=False)


@functools.partial(jax.jit, static_argnums=(2,))
def _hashshard_oracle(byte_rows: jax.Array, lengths: jax.Array,
                      n_shards: int):
    return hashshard_ref(byte_rows, lengths, n_shards)


def hashshard_route(byte_rows, lengths, n_shards: int = 64):
    """Batch-routing entry point for the sharded index: the compiled
    Pallas kernel on a TPU, its jitted jnp oracle on the CPU, where
    per-grid-step interpretation would dominate a routing hot path.
    Identical outputs either way (test_kernels pins them)."""
    fn = _hashshard_compiled if on_tpu() else _hashshard_oracle
    return fn(byte_rows, lengths, n_shards)
