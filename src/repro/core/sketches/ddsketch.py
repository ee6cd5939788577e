"""DDSketch [Masson et al., VLDB'19] — fully-mergeable quantile sketch with
relative-error guarantees. Icicle's default (paper §V-A4 adopts it for its
stable value accuracy: mean relative error < 0.01).

TPU-native formulation (DESIGN.md §2): the sketch state is a dense
log-bucket histogram, so

  - update  = bucketize + histogram accumulate (the Pallas ``ddsketch``
    kernel does this with a one-hot MXU matmul; this module is the jnp
    reference),
  - merge   = elementwise add  ==>  cross-device merge is a ``psum``,
  - vectorized over a leading *principal* axis: state (P, NBUCKETS).

Values <= min_value collapse into the zero bucket (DDSketch contract).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class DDSketchConfig:
    alpha: float = 0.01            # relative accuracy
    n_buckets: int = 2048
    offset: int = 128              # bucket index of value ~ gamma^-offset

    @property
    def gamma(self) -> float:
        return (1.0 + self.alpha) / (1.0 - self.alpha)

    @property
    def min_value(self) -> float:
        return self.gamma ** (-self.offset)

    @property
    def max_value(self) -> float:
        return self.gamma ** (self.n_buckets - self.offset - 1)

    def covers(self, max_value: float) -> bool:
        """Whether values up to max_value avoid top-bucket clipping. At
        alpha=0.01 you need ~1900 buckets to span [gamma^-128, 1e16];
        smaller bucket budgets must use coarser alpha."""
        return max_value <= self.max_value


DEFAULT = DDSketchConfig()


def init(cfg: DDSketchConfig, prefix: Tuple[int, ...] = ()) -> Dict:
    """Sketch state; all fields mergeable by elementwise combine."""
    return {
        "counts": jnp.zeros(prefix + (cfg.n_buckets,), jnp.float32),
        "zero_count": jnp.zeros(prefix, jnp.float32),
        "count": jnp.zeros(prefix, jnp.float32),
        "total": jnp.zeros(prefix, jnp.float32),
        "min": jnp.full(prefix, jnp.inf, jnp.float32),
        "max": jnp.full(prefix, -jnp.inf, jnp.float32),
    }


def bucket_index(cfg: DDSketchConfig, values: jax.Array) -> jax.Array:
    """values (N,) float -> bucket ids (N,) int32. Values <= min_value -> -1
    (zero bucket)."""
    v = values.astype(jnp.float32)
    safe = jnp.maximum(v, cfg.min_value)
    idx = jnp.ceil(jnp.log(safe) / math.log(cfg.gamma)).astype(jnp.int32) + cfg.offset
    idx = jnp.clip(idx, 0, cfg.n_buckets - 1)
    return jnp.where(v <= cfg.min_value, -1, idx)


def update(cfg: DDSketchConfig, state: Dict, values: jax.Array,
           mask: Optional[jax.Array] = None) -> Dict:
    """Single-principal update: state (NB,), values (N,)."""
    if mask is None:
        mask = jnp.ones_like(values, jnp.float32)
    mask = mask.astype(jnp.float32)
    idx = bucket_index(cfg, values)
    w_pos = jnp.where(idx >= 0, mask, 0.0)
    counts = state["counts"].at[jnp.maximum(idx, 0)].add(w_pos)
    big = jnp.where(mask > 0, values.astype(jnp.float32), jnp.inf)
    small = jnp.where(mask > 0, values.astype(jnp.float32), -jnp.inf)
    return {
        "counts": counts,
        "zero_count": state["zero_count"] + jnp.sum(jnp.where(idx < 0, mask, 0.0)),
        "count": state["count"] + jnp.sum(mask),
        "total": state["total"] + jnp.sum(values.astype(jnp.float32) * mask),
        "min": jnp.minimum(state["min"], jnp.min(big)),
        "max": jnp.maximum(state["max"], jnp.max(small)),
    }


def update_grouped(cfg: DDSketchConfig, state: Dict, values: jax.Array,
                   pids: jax.Array, n_principals: int,
                   mask: Optional[jax.Array] = None) -> Dict:
    """Grouped update: state (P, NB), values (N,), pids (N,) int32 in [0,P).

    This is the hot loop of the aggregate pipeline — the Pallas kernel
    ``kernels/ddsketch`` implements the same contraction with VMEM tiling.
    """
    if mask is None:
        mask = jnp.ones_like(values, jnp.float32)
    mask = mask.astype(jnp.float32)
    idx = bucket_index(cfg, values)
    w_pos = jnp.where(idx >= 0, mask, 0.0)
    v32 = values.astype(jnp.float32)
    counts = state["counts"].at[pids, jnp.maximum(idx, 0)].add(w_pos)
    zero = state["zero_count"].at[pids].add(jnp.where(idx < 0, mask, 0.0))
    count = state["count"].at[pids].add(mask)
    total = state["total"].at[pids].add(v32 * mask)
    big = jnp.where(mask > 0, v32, jnp.inf)
    small = jnp.where(mask > 0, v32, -jnp.inf)
    mn = state["min"].at[pids].min(big)
    mx = state["max"].at[pids].max(small)
    return {"counts": counts, "zero_count": zero, "count": count,
            "total": total, "min": mn, "max": mx}


def merge(s1: Dict, s2: Dict) -> Dict:
    return {
        "counts": s1["counts"] + s2["counts"],
        "zero_count": s1["zero_count"] + s2["zero_count"],
        "count": s1["count"] + s2["count"],
        "total": s1["total"] + s2["total"],
        "min": jnp.minimum(s1["min"], s2["min"]),
        "max": jnp.maximum(s1["max"], s2["max"]),
    }


def merge_psum(state: Dict, axis) -> Dict:
    """Cross-device merge inside shard_map: sketches are monoids, so the
    paper's Flink shuffle becomes a TPU all-reduce."""
    return {
        "counts": jax.lax.psum(state["counts"], axis),
        "zero_count": jax.lax.psum(state["zero_count"], axis),
        "count": jax.lax.psum(state["count"], axis),
        "total": jax.lax.psum(state["total"], axis),
        "min": jax.lax.pmin(state["min"], axis),
        "max": jax.lax.pmax(state["max"], axis),
    }


def merge_psum_scatter(state: Dict, axes) -> Dict:
    """Reduce-scatter merge (§Perf hillclimb): downstream quantile
    extraction needs each principal's sketch on ONE device, so the
    all-reduce's broadcast half is wasted — scatter principals across the
    reducing axes instead (half the wire bytes). min/max vectors are tiny:
    pmin/pmax + local slice."""
    axes = (axes,) if isinstance(axes, str) else tuple(axes)
    out = dict(state)
    for k in ("counts", "zero_count", "count", "total"):
        x = out[k]
        for ax in axes:
            x = jax.lax.psum_scatter(x, ax, scatter_dimension=0, tiled=True)
        out[k] = x
    # slice min/max to the same local principal range
    n_shard = 1
    idx = 0
    for ax in axes:
        size = jax.lax.axis_size(ax)
        idx = idx * size + jax.lax.axis_index(ax)
        n_shard *= size
    for k in ("min", "max"):
        full = jax.lax.pmin(out[k], axes) if k == "min" else \
            jax.lax.pmax(out[k], axes)
        p_loc = full.shape[0] // n_shard
        out[k] = jax.lax.dynamic_slice_in_dim(full, idx * p_loc, p_loc, 0)
    return out


def quantile(cfg: DDSketchConfig, state: Dict, q) -> jax.Array:
    """Vectorized quantile: state (..., NB), q scalar or (Q,). Returns
    (..., Q) if q is a vector else (...)."""
    qs = jnp.atleast_1d(jnp.asarray(q, jnp.float32))
    counts = state["counts"]
    zero = state["zero_count"][..., None]
    total_n = state["count"][..., None]
    rank = qs * jnp.maximum(total_n - 1.0, 0.0)          # (..., Q)
    cum = jnp.cumsum(counts, axis=-1)[..., None, :]       # (..., 1, NB) -> broadcast
    # searchsorted per quantile: first bucket where zero + cum > rank
    reached = (zero[..., None] + cum) > rank[..., None]   # (..., Q, NB)
    idx = jnp.argmax(reached, axis=-1)                    # (..., Q)
    g = cfg.gamma
    val = 2.0 * jnp.power(g, idx.astype(jnp.float32) - cfg.offset) / (g + 1.0)
    val = jnp.where(rank < zero, 0.0, val)
    val = jnp.clip(val, 0.0, jnp.where(jnp.isfinite(state["max"][..., None]),
                                       state["max"][..., None], jnp.inf))
    empty = (total_n == 0)
    val = jnp.where(empty, jnp.nan, val)
    if jnp.ndim(q) == 0:
        val = val[..., 0]
    return val


def summary(cfg: DDSketchConfig, state: Dict,
            qs=(0.10, 0.25, 0.50, 0.75, 0.90, 0.99)) -> Dict:
    """The aggregate-index record fields (Table III)."""
    quants = quantile(cfg, state, jnp.asarray(qs))
    return {
        "quantiles": quants,
        "min": state["min"],
        "max": state["max"],
        "mean": state["total"] / jnp.maximum(state["count"], 1.0),
        "total": state["total"],
        "count": state["count"],
    }
