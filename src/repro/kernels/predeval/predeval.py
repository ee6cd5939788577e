"""Fused multi-column predicate kernel (Pallas; DESIGN.md §13).

One grid pass over an arena epoch evaluates K stacked predicate
programs against the six Table-I columns and emits packed match
bitmaps: the kernel reads each touched column ONCE per row block and
amortizes that memory traffic across the whole program batch — the
HAIL per-partition-projection idea taken to its bandwidth-bound limit.

Layout per grid step j (row block of ``BLOCK_ROWS``):

- ``fcols`` (3, n_pad) float32 / ``icols`` (3, n_pad) int32 /
  ``alive`` (1, n_pad) int32 stream through in row blocks;
- the program arrays (see ref.py for the encoding; K padded to a
  multiple of 8 sublanes, set vectors as (K_set, 1) columns) and the
  bit-packing matrix are small and fully resident every step;
- ``out`` (k_pad, n_pad / 32) — bit (r % 32) of word ``out[k, r // 32]``
  is program k's verdict on row r.

Masks are float32 0/1 tiles (the TPU compiler cannot narrow byte masks
to i1 vectors), and the bits are packed on the MXU: a bf16 matmul of
the (K, BLOCK_ROWS) mask against a constant (BLOCK_ROWS, 2 * W)
matrix whose entries are 2^(r % 16) puts the low and high 16-bit
halves of every word side by side. Every product is an exact power of
two and each half sums to < 2^16, so the float32 accumulation is exact;
the halves are joined in int32 (bit 31 wraps negative with the same
pattern) and bitcast to uint32 outside the kernel.

Numerics contract (shared with ref.predeval_host / ref.predeval_ref,
bit-for-bit): RANGE compares the value cast to float32 against
pre-widened inclusive bounds — a SUPERSET of the exact predicate,
trimmed by the caller's exact verify; MASK and NOTIN are exact integer
ops; dead rows never match.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.kernels.predeval.ref import (BLOCK_ROWS, FLOAT_COLS, OP_MASK,
                                        OP_RANGE)


#: 32-bit words per row block; the packing matmul emits 2 * WORDS lanes
WORDS = BLOCK_ROWS // 32


@functools.cache
def _pack_matrix() -> np.ndarray:
    """(BLOCK_ROWS, 2 * WORDS): row r feeds word r // 32 with weight
    2^(r % 16), in the low-half lanes [0, WORDS) for bits 0..15 and the
    high-half lanes [WORDS, 2 * WORDS) for bits 16..31."""
    r = np.arange(BLOCK_ROWS)
    m = np.zeros((BLOCK_ROWS, 2 * WORDS), np.float32)
    m[r, r // 32 + WORDS * ((r % 32) >= 16)] = 2.0 ** (r % 16)
    return m


def _predeval_kernel(ops_ref, lo_ref, hi_ref, msk_ref, setrows_ref,
                     setcol_ref, setvals_ref, pack_ref, fcols_ref,
                     icols_ref, alive_ref, out_ref, *, has_set: bool):
    k_pad = ops_ref.shape[0]
    blk = alive_ref.shape[1]
    match = jnp.broadcast_to(
        jnp.where(alive_ref[...] != 0, 1.0, 0.0), (k_pad, blk))
    for ci in range(ops_ref.shape[1]):         # static: 6 columns
        opc = ops_ref[:, ci:ci + 1]            # (k_pad, 1)
        if ci < FLOAT_COLS:
            v = fcols_ref[ci:ci + 1, :]        # (1, blk)
        else:
            vi = icols_ref[ci - FLOAT_COLS:ci - FLOAT_COLS + 1, :]
            v = vi.astype(jnp.float32)
        in_rng = ((v >= lo_ref[:, ci:ci + 1])
                  & (v <= hi_ref[:, ci:ci + 1]))
        match = jnp.where((opc == OP_RANGE) & ~in_rng, 0.0, match)
        if ci >= FLOAT_COLS:
            miss = (vi & msk_ref[:, ci:ci + 1]) == 0
            match = jnp.where((opc == OP_MASK) & miss, 0.0, match)
    if has_set:
        sel = setcol_ref[...]                  # (ks, 1)
        ic = [icols_ref[i:i + 1, :] for i in range(3)]
        vi = jnp.where(sel == FLOAT_COLS, ic[0],
                       jnp.where(sel == FLOAT_COLS + 1, ic[1], ic[2]))
        hit = jnp.zeros(vi.shape, jnp.float32)  # (ks, blk)
        for s in range(setvals_ref.shape[1]):  # static unroll
            hit = jnp.where(vi == setvals_ref[:, s:s + 1], 1.0, hit)
        k_iota = jax.lax.broadcasted_iota(jnp.int32, (k_pad, 1), 0)
        for t in range(setrows_ref.shape[0]):  # static: K_set programs
            # one-hot row select instead of scatter (padding entries
            # carry setrows == k_pad and select nothing)
            match = jnp.where((k_iota == setrows_ref[t:t + 1, :])
                              & (hit[t:t + 1, :] > 0), 0.0, match)
    halves = jnp.dot(match.astype(jnp.bfloat16), pack_ref[...],
                     preferred_element_type=jnp.float32).astype(jnp.int32)
    w = out_ref.shape[1]
    out_ref[...] = halves[:, :w] | (halves[:, w:] << 16)


def predeval(fcols, icols, alive, ops, lo, hi, msk, setrows, setcol,
             setvals, has_set: bool, interpret: bool = False):
    """(k_pad, n_pad / 32) uint32 packed bitmaps; ``n_pad`` (the arena
    row count) must be a multiple of ``BLOCK_ROWS``."""
    k_pad, n_cols = ops.shape
    n_pad = fcols.shape[1]
    assert n_pad % BLOCK_ROWS == 0, n_pad
    k8 = -(-k_pad // 8) * 8                    # whole sublane tiles
    if k8 != k_pad:                            # OP_NONE rows, sliced off
        rows = ((0, k8 - k_pad), (0, 0))
        ops, lo, hi, msk = (jnp.pad(a, rows) for a in (ops, lo, hi, msk))
    grid = (n_pad // BLOCK_ROWS,)
    ks, s = setvals.shape
    whole = lambda *shape: pl.BlockSpec(shape, lambda j: (0,) * len(shape))
    words = pl.pallas_call(
        functools.partial(_predeval_kernel, has_set=has_set),
        grid=grid,
        in_specs=[
            whole(k8, n_cols),                          # ops
            whole(k8, n_cols),                          # lo
            whole(k8, n_cols),                          # hi
            whole(k8, n_cols),                          # msk
            whole(ks, 1),                               # setrows
            whole(ks, 1),                               # setcol
            whole(ks, s),                               # setvals
            whole(BLOCK_ROWS, 2 * WORDS),               # pack matrix
            pl.BlockSpec((3, BLOCK_ROWS), lambda j: (0, j)),   # fcols
            pl.BlockSpec((3, BLOCK_ROWS), lambda j: (0, j)),   # icols
            pl.BlockSpec((1, BLOCK_ROWS), lambda j: (0, j)),   # alive
        ],
        out_specs=pl.BlockSpec((k8, WORDS), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((k8, n_pad // 32), jnp.int32),
        interpret=interpret,
        name="predeval",
    )(ops, lo, hi, msk, setrows.reshape(ks, 1), setcol.reshape(ks, 1),
      setvals, jnp.asarray(_pack_matrix(), jnp.bfloat16), fcols, icols,
      alive.reshape(1, n_pad))
    return jax.lax.bitcast_convert_type(words[:k_pad], jnp.uint32)
