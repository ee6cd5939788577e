"""Pallas TPU kernels for the index's device hot paths. Each package
holds the kernel module, ``ops.py`` (the entry points callers use) and
``ref.py`` (jnp and numpy oracles the kernel is pinned against).

The platform picks the form a kernel runs in, at call time: on a TPU
the compiled Pallas kernel, always; on the CPU (tests, development) its
jitted jnp oracle, while the kernel tests run each kernel in interpret
mode. Any other backend is an error rather than a guess.
"""
from __future__ import annotations

import jax


def on_tpu() -> bool:
    """True when the default backend is a TPU (run compiled kernels),
    False on the CPU; raises on any other backend."""
    backend = jax.default_backend()
    if backend == "tpu":
        return True
    if backend == "cpu":
        return False
    raise RuntimeError(
        f"no kernel form for JAX backend {backend!r}: expected 'tpu' "
        "(compiled Pallas) or 'cpu' (interpret mode / jnp oracle)")
