"""Pallas TPU kernel: grouped DDSketch update (the aggregate pipeline's hot
loop).

TPU-native formulation: instead of a scatter (bad on TPU), the histogram
accumulation is a ONE-HOT MXU CONTRACTION —

    counts[p, b] += sum_r onehot_P[r, p] * onehot_B[r, b]
                 == (onehot_P^T @ onehot_B)[p, b]

i.e. a (P_BLK x ROWS) @ (NB x ROWS)^T matmul per tile, contracted over
the row (lane) axis, which the MXU eats at full rate (all dims padded to
multiples of 128). The remaining per-principal moments
(count/total/min/max/zero) are lane reductions over the same one-hot.

Bucket ids come from ``sketches.ddsketch.bucket_index`` (XLA, fused
ahead of the kernel), so the kernel bins exactly as the reference does.
Rows stream in as (1, ROWS) lane-major blocks and both one-hots are
built as (classes, ROWS) from a sublane iota, so no row vector is ever
reshaped into a column (the TPU compiler refuses that cast for masks).
Moments come out as (P_BLK, 1) columns.

Grid: (P_blocks, N_blocks); output blocks are indexed by the principal
block only, so they stay VMEM-resident across the inner (row) grid
dimension and accumulate in place.

VMEM budget per step (defaults ROWS=512, P_BLK=128, NB=2048, f32):
  onehot_P 128x512 (256 KB) + onehot_B 2048x512 (4 MB)
  + counts 128x2048 (1 MB) + row vectors  ==>  ~5.5 MB  (< 16 MB VMEM).
"""
from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.sketches import ddsketch as dds
from repro.core.sketches.ddsketch import DDSketchConfig

NEG_BIG = -3.0e38
POS_BIG = 3.0e38


def _kernel(idx_ref, vals_ref, pids_ref, mask_ref,
            counts_ref, zero_ref, cnt_ref, tot_ref, min_ref, max_ref,
            *, p_block: int):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        counts_ref[...] = jnp.zeros_like(counts_ref)
        zero_ref[...] = jnp.zeros_like(zero_ref)
        cnt_ref[...] = jnp.zeros_like(cnt_ref)
        tot_ref[...] = jnp.zeros_like(tot_ref)
        min_ref[...] = jnp.full_like(min_ref, POS_BIG)
        max_ref[...] = jnp.full_like(max_ref, NEG_BIG)

    idx = idx_ref[...]                             # (1, ROWS) int32, -1 = zero
    v = vals_ref[...]                              # (1, ROWS) float32
    pid = pids_ref[...]                            # (1, ROWS) int32 (global)
    m = mask_ref[...]                              # (1, ROWS) float32
    nb = counts_ref.shape[1]

    # principal one-hot restricted to this block, weighted by the mask
    p_iota = (jax.lax.broadcasted_iota(jnp.int32, (p_block, 1), 0)
              + pl.program_id(0) * p_block)
    in_p = pid == p_iota                           # (P_BLK, ROWS)
    onehot_p = jnp.where(in_p, m, 0.0)

    # bucket one-hot (zero-bucket rows carry -1 and match no bucket)
    onehot_b = jnp.where(
        idx == jax.lax.broadcasted_iota(jnp.int32, (nb, 1), 0),
        1.0, 0.0)                                  # (NB, ROWS)

    # MXU: histogram block accumulate
    counts_ref[...] += jax.lax.dot_general(
        onehot_p, onehot_b, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)

    # VPU: per-principal moments
    zero_ref[...] += jnp.sum(jnp.where(idx < 0, onehot_p, 0.0), axis=1,
                             keepdims=True)
    cnt_ref[...] += jnp.sum(onehot_p, axis=1, keepdims=True)
    tot_ref[...] += jnp.sum(onehot_p * v, axis=1, keepdims=True)
    live = in_p & (m > 0)
    min_ref[...] = jnp.minimum(
        min_ref[...],
        jnp.min(jnp.where(live, v, POS_BIG), axis=1, keepdims=True))
    max_ref[...] = jnp.maximum(
        max_ref[...],
        jnp.max(jnp.where(live, v, NEG_BIG), axis=1, keepdims=True))


def grouped_update_pallas(cfg: DDSketchConfig, values: jax.Array,
                          pids: jax.Array, mask: jax.Array,
                          n_principals: int, *, rows: int = 512,
                          p_block: int = 128,
                          interpret: bool = True) -> Dict[str, jax.Array]:
    """Returns the DELTA sketch state for this batch (merge into running
    state with sketches.ddsketch.merge)."""
    n = values.shape[0]
    n_pad = -(-n // rows) * rows
    p_pad = -(-n_principals // p_block) * p_block
    nb = cfg.n_buckets

    def row(x, dtype):
        return jnp.pad(x.astype(dtype), (0, n_pad - n)).reshape(1, n_pad)

    grid = (p_pad // p_block, n_pad // rows)
    col = jax.ShapeDtypeStruct((p_pad, 1), jnp.float32)
    col_spec = pl.BlockSpec((p_block, 1), lambda i, j: (i, 0))
    counts, zero, cnt, tot, mn, mx = pl.pallas_call(
        functools.partial(_kernel, p_block=p_block),
        grid=grid,
        in_specs=[pl.BlockSpec((1, rows), lambda i, j: (0, j))] * 4,
        out_specs=(pl.BlockSpec((p_block, nb), lambda i, j: (i, 0)),)
        + (col_spec,) * 5,
        out_shape=(jax.ShapeDtypeStruct((p_pad, nb), jnp.float32),)
        + (col,) * 5,                  # zero, count, total, min, max
        interpret=interpret,
        name="ddsketch_grouped_update",
    )(row(dds.bucket_index(cfg, values), jnp.int32),
      row(values, jnp.float32), row(pids, jnp.int32),
      row(mask, jnp.float32))

    sl = slice(0, n_principals)
    mn, mx = mn[sl, 0], mx[sl, 0]
    return {
        "counts": counts[sl],
        "zero_count": zero[sl, 0],
        "count": cnt[sl, 0],
        "total": tot[sl, 0],
        "min": jnp.where(mn >= POS_BIG, jnp.inf, mn),
        "max": jnp.where(mx <= NEG_BIG, -jnp.inf, mx),
    }
