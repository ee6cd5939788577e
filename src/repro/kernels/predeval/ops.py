"""Dispatch layer for the fused predicate kernel (DESIGN.md §13).

Same contract as the sibling kernel packages (ddsketch / segstats /
hashshard): callers get one entry point per op, and the platform picks
its form (``repro.kernels.on_tpu``). On a TPU ``predeval_words`` runs
the compiled Pallas kernel; on the CPU it runs the jitted whole-array
jax.numpy oracle, because per-grid-step Pallas interpretation dominates
there. Both are bit-for-bit identical to the numpy host oracle in
ref.py on the packed bitmaps — tests/test_predeval.py pins it.

``Arena`` is the device-resident stacked column slab for one shard at
one mutation epoch: (3, n_pad) float32 + (3, n_pad) int32 + alive,
padded to a power-of-two multiple of ``BLOCK_ROWS`` so the jitted
evaluators compile once per shape bucket. The query engine caches one
per (shard, epoch) — rebuilding it is the per-epoch cost that the K-way
program batching then amortizes across the query stream.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import on_tpu
from repro.kernels.predeval import ref
from repro.kernels.predeval.ref import BLOCK_ROWS, FLOAT_COLS, PRED_COLUMNS


def _pad_rows(n: int) -> int:
    p = BLOCK_ROWS
    while p < n:
        p *= 2
    return p


@dataclasses.dataclass
class Arena:
    """Stacked column slab for one shard epoch, as device arrays. ``n``
    is the true row count; rows n..n_pad-1 are zero-padding with
    alive=0."""

    fcols: jax.Array       # (3, n_pad) float32
    icols: jax.Array       # (3, n_pad) int32
    alive: jax.Array       # (n_pad,) int32
    n: int
    n_pad: int

    @property
    def nbytes(self) -> int:
        """Bytes one fused pass streams (the roofline numerator)."""
        return self.n_pad * (3 * 4 + 3 * 4 + 4)


def pack_arena(columns: Dict[str, np.ndarray], alive: np.ndarray,
               n: int) -> Arena:
    """Build the slab from primary-index arenas (first ``n`` slots —
    ``len(slot_map)`` on a live index, ``snapshot.n`` on a pinned
    view). Missing columns materialize as zeros, like ``live()``."""
    n_pad = _pad_rows(max(n, 1))
    fcols = np.zeros((FLOAT_COLS, n_pad), np.float32)
    icols = np.zeros((len(PRED_COLUMNS) - FLOAT_COLS, n_pad), np.int32)
    for i, col in enumerate(PRED_COLUMNS):
        arr = columns.get(col)
        if arr is None:
            continue
        if i < FLOAT_COLS:
            fcols[i, :n] = arr[:n]
        else:
            icols[i - FLOAT_COLS, :n] = arr[:n]
    av = np.zeros(n_pad, np.int32)
    av[:n] = alive[:n]
    return Arena(jnp.asarray(fcols), jnp.asarray(icols), jnp.asarray(av),
                 n, n_pad)


@functools.lru_cache(maxsize=None)
def _jitted(has_set: bool, use_pallas: bool):
    if use_pallas:
        from repro.kernels.predeval.predeval import predeval

        def fn(fcols, icols, alive, ops, lo, hi, msk, setrows, setcol,
               setvals):
            return predeval(fcols, icols, alive, ops, lo, hi, msk,
                            setrows, setcol, setvals, has_set=has_set)
    else:
        def fn(fcols, icols, alive, ops, lo, hi, msk, setrows, setcol,
               setvals):
            return ref.predeval_ref(fcols, icols, alive, ops, lo, hi,
                                    msk, setrows, setcol, setvals,
                                    has_set=has_set)
    return jax.jit(fn)


def predeval_device(arena: Arena, progs: ref.Programs) -> jax.Array:
    """(k_pad, n_pad/32) uint32 packed bitmaps for the program batch,
    left on the device — one fused read of the arena regardless of K."""
    fn = _jitted(progs.has_set, on_tpu())
    return fn(arena.fcols, arena.icols, arena.alive,
              jnp.asarray(progs.ops), jnp.asarray(progs.lo),
              jnp.asarray(progs.hi), jnp.asarray(progs.msk),
              jnp.asarray(progs.setrows), jnp.asarray(progs.setcol),
              jnp.asarray(progs.setvals))


def predeval_words(arena: Arena, progs: ref.Programs) -> np.ndarray:
    """``predeval_device``'s bitmaps, read back to the host."""
    return np.asarray(predeval_device(arena, progs))


def bitmap_slots(words: np.ndarray, k: int, n: int) -> np.ndarray:
    """Program k's candidate slot ids (sorted int64) from the packed
    bitmaps, clamped to the true row count."""
    bits = ref.unpack_bits(words[k], n)
    return np.flatnonzero(bits).astype(np.int64)
