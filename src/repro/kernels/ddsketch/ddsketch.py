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

Streams. ``pids`` and ``mask`` carry a leading stream axis ``(S, N)``:
S principal streams over the same values (a record's uid slot, gid slot
and directory levels), so they share the bucket ids and the bucket
one-hot. The principal one-hot is summed over the streams,

    onehot_P[p, r] = sum_s mask[s, r] * (pids[s, r] == p),

and one contraction gives the sum of the S single-stream updates, which
is what merging them one after another gives: every field of the state
is additive or a min/max. count/zero/total come from that f32 sum; min
and max from the rows any stream puts in the principal with a non-zero
mask. ``bucket_index`` runs once per call.

Exactness. The contraction is one bf16 MXU pass with f32 accumulation
(``preferred_element_type``, default precision). Its operands are
one-hot entries only: 0/1 on the bucket side and 0..S on the principal
side, all exact in bf16; the products are exact in f32, and every
partial sum is an integer at most S x N, below 2^24 for any batch the
callers make (S x N <= 5 x 2^18), so it adds exactly in f32. Hence the
bucket counts are bit-identical to a HIGHEST-precision (six-pass)
product. Values never pass through bf16: the moments are VPU sums,
minima and maxima over the f32 values. This holds for a 0/1 ``mask``,
which is every caller's (the snapshot aggregate step passes stream
presence x principal-shard selection x row validity, event ingest the
stream validity, ``bench/record_trace.py`` ones); a weighted mask is
``sketches.ddsketch.update_grouped``'s business, not this kernel's.

Bucket ids come from ``sketches.ddsketch.bucket_index`` (XLA, fused
ahead of the kernel), so the kernel bins exactly as the reference does.
Rows stream in as (1, ROWS) / (S, ROWS) lane-major blocks and both
one-hots are built as (classes, ROWS) from a sublane iota, so no row
vector is ever reshaped into a column (the TPU compiler refuses that
cast for masks). Moments come out as (P_BLK, 1) columns.

Grid: (P_blocks, N_blocks); output blocks are indexed by the principal
block only, so they stay VMEM-resident across the inner (row) grid
dimension and accumulate in place.

VMEM budget per step (defaults ROWS=512, P_BLK=128, NB=2048, S<=8):
  onehot_B 2048x512 bf16 (2 MB; its f32 compare/select before the cast
  up to 4 MB) + onehot_P 128x512 f32 and bf16 (384 KB) + the (128, 2048)
  f32 product and the double-buffered counts block (3 MB)
  + row and stream blocks  ==>  ~10 MB  (< 16 MB scoped VMEM).
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
    nb = counts_ref.shape[1]

    # principal one-hot restricted to this block, summed over the streams
    # and weighted by their masks; live = some stream puts the row here
    p_iota = (jax.lax.broadcasted_iota(jnp.int32, (p_block, 1), 0)
              + pl.program_id(0) * p_block)
    onehot_p = None
    live = None
    for s in range(pids_ref.shape[0]):
        pid = pids_ref[pl.ds(s, 1), :]             # (1, ROWS) int32 (global)
        m = mask_ref[pl.ds(s, 1), :]               # (1, ROWS) float32
        in_p = pid == p_iota                       # (P_BLK, ROWS)
        w = jnp.where(in_p, m, 0.0)
        hit = in_p & (m > 0)
        onehot_p = w if onehot_p is None else onehot_p + w
        live = hit if live is None else live | hit

    # bucket one-hot (zero-bucket rows carry -1 and match no bucket)
    onehot_b = jnp.where(
        idx == jax.lax.broadcasted_iota(jnp.int32, (nb, 1), 0),
        1.0, 0.0).astype(jnp.bfloat16)             # (NB, ROWS)

    # MXU: histogram block accumulate, one exact bf16 pass
    counts_ref[...] += jax.lax.dot_general(
        onehot_p.astype(jnp.bfloat16), onehot_b, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    # VPU: per-principal moments, in f32
    zero_ref[...] += jnp.sum(jnp.where(idx < 0, onehot_p, 0.0), axis=1,
                             keepdims=True)
    cnt_ref[...] += jnp.sum(onehot_p, axis=1, keepdims=True)
    tot_ref[...] += jnp.sum(onehot_p * v, axis=1, keepdims=True)
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
    state with sketches.ddsketch.merge). ``values`` (N,); ``pids`` and
    ``mask`` (S, N) for S streams over those values, or (N,) for one;
    ``mask`` is 0/1 (module docstring)."""
    n = values.shape[0]
    n_pad = -(-n // rows) * rows
    p_pad = -(-n_principals // p_block) * p_block
    nb = cfg.n_buckets

    def row(x, dtype):                 # (S, n_pad); an (N,) vector is S = 1
        x = jnp.atleast_2d(x.astype(dtype))
        return jnp.pad(x, ((0, 0), (0, n_pad - n)))

    pids, mask = row(pids, jnp.int32), row(mask, jnp.float32)
    n_streams = pids.shape[0]
    grid = (p_pad // p_block, n_pad // rows)
    col = jax.ShapeDtypeStruct((p_pad, 1), jnp.float32)
    col_spec = pl.BlockSpec((p_block, 1), lambda i, j: (i, 0))
    counts, zero, cnt, tot, mn, mx = pl.pallas_call(
        functools.partial(_kernel, p_block=p_block),
        grid=grid,
        in_specs=[pl.BlockSpec((1, rows), lambda i, j: (0, j))] * 2
        + [pl.BlockSpec((n_streams, rows), lambda i, j: (0, j))] * 2,
        out_specs=(pl.BlockSpec((p_block, nb), lambda i, j: (i, 0)),)
        + (col_spec,) * 5,
        out_shape=(jax.ShapeDtypeStruct((p_pad, nb), jnp.float32),)
        + (col,) * 5,                  # zero, count, total, min, max
        interpret=interpret,
        name="ddsketch_grouped_update",
    )(row(dds.bucket_index(cfg, values), jnp.int32),
      row(values, jnp.float32), pids, mask)

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
