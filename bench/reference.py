"""Plain reference implementations the benchmark compares the system
with. Nothing here imports the program: the same operations on the same
data, written straightforwardly in numpy and Python.

- ``fnv1a``: the FNV-1a 32-bit hash of every path (the route's hash);
- ``counting``/``sketch``: the per-(principal, shard) object counts and
  the grouped DDSketch of the aggregate workflow, in float64.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Sequence

import numpy as np

FNV_OFFSET = np.uint32(0x811C9DC5)
FNV_PRIME = np.uint32(0x01000193)


def fnv1a(paths: Sequence[str]) -> np.ndarray:
    """FNV-1a 32-bit over each path's UTF-8 bytes, one byte column at a
    time, rows past their length left as they are."""
    raw = [p.encode("utf-8", "surrogatepass") for p in paths]
    n = len(raw)
    lens = np.fromiter((len(b) for b in raw), np.int64, n)
    w = int(lens.max(initial=0))
    mat = np.zeros((n, w), np.uint8)
    for i, b in enumerate(raw):
        mat[i, :len(b)] = np.frombuffer(b, np.uint8)
    h = np.full(n, FNV_OFFSET, np.uint32)
    for j in range(w):
        on = j < lens
        h = np.where(on, (h ^ mat[:, j]) * FNV_PRIME, h).astype(np.uint32)
    return h


# ---------------------------------------------------------------------------
# counting and aggregate workflows
# ---------------------------------------------------------------------------

def streams(rows: Dict[str, np.ndarray]):
    """(principal id, weight) per principal stream: user, group, and one
    directory-prefix level each (weight 0 where the level is absent)."""
    n = len(rows["uid_slot"])
    out = [(rows["uid_slot"].astype(np.int64), np.ones(n)),
           (rows["gid_slot"].astype(np.int64), np.ones(n))]
    ds = rows["dir_slots"]
    for li in range(ds.shape[1]):
        pid = ds[:, li].astype(np.int64)
        out.append((np.maximum(pid, 0), (pid >= 0).astype(np.float64)))
    return out


def counting(rows: Dict[str, np.ndarray], n_principals: int,
             n_shards: int) -> np.ndarray:
    c = np.zeros(n_principals * n_shards)
    sid = rows["shard_id"].astype(np.int64)
    for pid, w in streams(rows):
        c += np.bincount(pid * n_shards + sid, weights=w,
                         minlength=n_principals * n_shards)
    return c.reshape(n_principals, n_shards)


def bucket_index(v: np.ndarray, alpha: float, n_buckets: int,
                 offset: int) -> np.ndarray:
    """DDSketch bucket of each value (float64 logarithm), -1 for values
    at or below the smallest bucket."""
    gamma = (1.0 + alpha) / (1.0 - alpha)
    vmin = gamma ** (-offset)
    v64 = np.asarray(v, np.float64)
    idx = np.ceil(np.log(np.maximum(v64, vmin)) / math.log(gamma)
                  ).astype(np.int64) + offset
    idx = np.clip(idx, 0, n_buckets - 1)
    return np.where(np.asarray(v, np.float32) <= np.float32(vmin), -1, idx)


def sketch(rows: Dict[str, np.ndarray], attrs: Sequence[str],
           n_principals: int, alpha: float, n_buckets: int, offset: int,
           value_dtype=np.float32) -> Dict[str, np.ndarray]:
    """Grouped DDSketch state (principals, attributes, ...) of the rows:
    bucket counts, zero count, count, total, min and max."""
    P, A = n_principals, len(attrs)
    counts = np.zeros((P, A, n_buckets))
    zero = np.zeros((P, A))
    count = np.zeros((P, A))
    total = np.zeros((P, A))
    mn = np.full((P, A), np.inf)
    mx = np.full((P, A), -np.inf)
    for ai, attr in enumerate(attrs):
        v = np.asarray(rows[attr]).astype(value_dtype).astype(np.float64)
        idx = bucket_index(v, alpha, n_buckets, offset)
        for pid, w in streams(rows):
            on = w > 0
            pos = on & (idx >= 0)
            counts[:, ai] += np.bincount(
                pid[pos] * n_buckets + idx[pos], weights=w[pos],
                minlength=P * n_buckets).reshape(P, n_buckets)
            zero[:, ai] += np.bincount(pid[on & (idx < 0)],
                                       weights=w[on & (idx < 0)],
                                       minlength=P)
            count[:, ai] += np.bincount(pid[on], weights=w[on],
                                        minlength=P)
            total[:, ai] += np.bincount(pid[on], weights=v[on] * w[on],
                                        minlength=P)
            if on.any():
                np.minimum.at(mn[:, ai], pid[on], v[on])
                np.maximum.at(mx[:, ai], pid[on], v[on])
    return {"counts": counts, "zero_count": zero, "count": count,
            "total": total, "min": mn, "max": mx}


def merge(a: Optional[Dict], b: Dict) -> Dict:
    if a is None:
        return {k: v.copy() for k, v in b.items()}
    return {"counts": a["counts"] + b["counts"],
            "zero_count": a["zero_count"] + b["zero_count"],
            "count": a["count"] + b["count"],
            "total": a["total"] + b["total"],
            "min": np.minimum(a["min"], b["min"]),
            "max": np.maximum(a["max"], b["max"])}


def bucket_shift(got: np.ndarray, want: np.ndarray) -> float:
    """Mass moved between buckets, in bucket steps per observation: the
    summed |CDF difference| over every sketch's buckets, over the total
    count. Values that land one bucket off add 1 each."""
    g = np.cumsum(np.asarray(got, np.float64), axis=-1)
    w = np.cumsum(np.asarray(want, np.float64), axis=-1)
    return float(np.abs(g - w).sum() / max(w[..., -1].sum(), 1.0))
