"""Pallas TPU kernel: fused per-(principal, shard) counting — the counting
pipeline's hot loop (paper §IV-A2).

Computes counts[p, s] += mask for every row, as a one-hot MXU contraction
over the row axis (principal one-hot @ shard one-hot^T), plus fused
per-principal sum/min/max of an attribute column (used for quick
capacity reports without a full sketch pass).

Rows stream in as (1, ROWS) lane-major blocks and both one-hots are
built as (classes, ROWS) from a sublane iota, so no row vector is ever
reshaped into a column (the TPU compiler refuses that cast for masks).
Per-principal moments are (P_BLK, 1) lane reductions of the same
one-hot; the shard axis is padded to whole 128-lane tiles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_BIG = -3.0e38
POS_BIG = 3.0e38


def _kernel(pids_ref, sids_ref, vals_ref, mask_ref,
            counts_ref, sum_ref, min_ref, max_ref, *, p_block: int):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        counts_ref[...] = jnp.zeros_like(counts_ref)
        sum_ref[...] = jnp.zeros_like(sum_ref)
        min_ref[...] = jnp.full_like(min_ref, POS_BIG)
        max_ref[...] = jnp.full_like(max_ref, NEG_BIG)

    pid = pids_ref[...]                            # (1, ROWS) int32
    v = vals_ref[...]                              # (1, ROWS) float32
    m = mask_ref[...]                              # (1, ROWS) float32
    s_pad = counts_ref.shape[1]

    p_iota = (jax.lax.broadcasted_iota(jnp.int32, (p_block, 1), 0)
              + pl.program_id(0) * p_block)
    in_p = pid == p_iota                           # (P_BLK, ROWS)
    onehot_p = jnp.where(in_p, m, 0.0)             # weighted by mask
    onehot_s = jnp.where(
        sids_ref[...] == jax.lax.broadcasted_iota(jnp.int32, (s_pad, 1), 0),
        1.0, 0.0)                                  # (S_PAD, ROWS)

    counts_ref[...] += jax.lax.dot_general(
        onehot_p, onehot_s, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)
    sum_ref[...] += jnp.sum(onehot_p * v, axis=1, keepdims=True)
    live = in_p & (m > 0)
    min_ref[...] = jnp.minimum(
        min_ref[...],
        jnp.min(jnp.where(live, v, POS_BIG), axis=1, keepdims=True))
    max_ref[...] = jnp.maximum(
        max_ref[...],
        jnp.max(jnp.where(live, v, NEG_BIG), axis=1, keepdims=True))


def segstats_pallas(pids: jax.Array, sids: jax.Array, values: jax.Array,
                    mask: jax.Array, n_principals: int, n_shards: int = 64,
                    *, rows: int = 512, p_block: int = 128,
                    interpret: bool = True):
    n = pids.shape[0]
    n_pad = -(-n // rows) * rows
    p_pad = -(-n_principals // p_block) * p_block
    s_pad = -(-n_shards // 128) * 128

    def row(x, dtype):
        return jnp.pad(x.astype(dtype), (0, n_pad - n)).reshape(1, n_pad)

    grid = (p_pad // p_block, n_pad // rows)
    col = pl.BlockSpec((p_block, 1), lambda i, j: (i, 0))
    counts, s, mn, mx = pl.pallas_call(
        functools.partial(_kernel, p_block=p_block),
        grid=grid,
        in_specs=[pl.BlockSpec((1, rows), lambda i, j: (0, j))] * 4,
        out_specs=(pl.BlockSpec((p_block, s_pad), lambda i, j: (i, 0)),
                   col, col, col),
        out_shape=(jax.ShapeDtypeStruct((p_pad, s_pad), jnp.float32),
                   jax.ShapeDtypeStruct((p_pad, 1), jnp.float32),
                   jax.ShapeDtypeStruct((p_pad, 1), jnp.float32),
                   jax.ShapeDtypeStruct((p_pad, 1), jnp.float32)),
        interpret=interpret,
        name="segstats",
    )(row(pids, jnp.int32), row(sids, jnp.int32),
      row(values, jnp.float32), row(mask, jnp.float32))
    sl = slice(0, n_principals)
    s, mn, mx = s[sl, 0], mn[sl, 0], mx[sl, 0]
    return {"counts": counts[sl, :n_shards], "sum": s,
            "min": jnp.where(mn >= POS_BIG, jnp.inf, mn),
            "max": jnp.where(mx <= NEG_BIG, -jnp.inf, mx)}
