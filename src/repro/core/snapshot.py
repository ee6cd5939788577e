"""Snapshot pipelines (paper §IV-A): primary, counting, aggregate.

Flink-on-Kafka becomes shard_map-on-mesh (DESIGN.md §2):

- rows shard over the DP axes (a "KPU" = a mesh device's row shard),
- principals (user/group/dir-prefix slots) shard over the "model" axis,
- the counting reduce is a one-hot segment-sum, merged with ``psum``,
- the aggregate reduce is a grouped DDSketch update (Pallas kernel on the
  hot path), merged with ``psum`` — sketches are monoids, so the paper's
  cross-KPU shuffle is literally an all-reduce here.

Host-side stages mirror the paper: preprocessing (assign principal slots,
directory-prefix expansion between ``dir_min``/``dir_max``), Globus-Search
record batching (10 MB / 5 s), and the recursive-directory-count
post-processing script.
"""
from __future__ import annotations

import dataclasses
import json
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.core import metadata as md
from repro.core.sketches import ddsketch as dds
from repro.kernels.ddsketch import ops as dd_ops

ATTRS = ("size", "atime", "ctime", "mtime")


@dataclasses.dataclass(frozen=True)
class PipelineConfig:
    n_users: int = 256
    n_groups: int = 64
    n_dirs: int = 1024             # directory-prefix slots
    dir_min: int = 1
    dir_max: int = 3               # aggregate prefixes at depths [min, max]
    n_shards: int = 64             # crc32-style intra-principal shards
    sketch: dds.DDSketchConfig = dds.DEFAULT
    batch_bytes: int = 10 * 1024 * 1024   # Globus Search ingest limit
    batch_timeout_s: float = 5.0

    @property
    def n_principals(self) -> int:
        return self.n_users + self.n_groups + self.n_dirs


# ---------------------------------------------------------------------------
# Preprocessing (host): rows -> principal slots (paper's "preprocessed CSVs")
# ---------------------------------------------------------------------------

def preprocess(table: md.MetadataTable, cfg: PipelineConfig) -> Dict[str, np.ndarray]:
    """Numeric row view + principal slot ids. Directory prefixes are
    expanded per row for each depth in [dir_min, dir_max].

    Vectorized: per-DIRECTORY prefix slots are computed once over the
    (small) dir table, then files inherit their parent dir's prefix row —
    the per-file work is just the crc32 shard hash (the paper's scheme).
    """
    levels = cfg.dir_max - cfg.dir_min + 1
    dir_rows = np.nonzero(table.type == md.TYPE_DIR)[0]
    dir_prefix = {}
    base = cfg.n_users + cfg.n_groups
    dir_slot_rows = np.full((len(table), levels), -1, np.int64)
    # ancestor paths per dir via parent pointers (dirs are few)
    for d in dir_rows:
        chain = []
        v = d
        guard = 0
        while v >= 0 and guard < 128:
            chain.append(v)
            v = int(table.parent[v])
            guard += 1
        chain.reverse()  # root .. d
        for li, depth in enumerate(range(cfg.dir_min, cfg.dir_max + 1)):
            if depth < len(chain):
                anc = chain[depth]
                slot = dir_prefix.setdefault(
                    anc, md.path_hash(table.paths[anc]) % cfg.n_dirs)
                dir_slot_rows[d, li] = base + slot

    file_mask = table.type != md.TYPE_DIR
    files = table.select(file_mask)
    n = len(files)
    uid_slot = files.uid.astype(np.int64) % cfg.n_users
    gid_slot = cfg.n_users + files.gid.astype(np.int64) % cfg.n_groups
    parents = np.clip(files.parent.astype(np.int64), 0, len(table) - 1)
    dir_slots = dir_slot_rows[parents]

    shard_id = np.fromiter(
        (md.crc32_shard(p.encode(), cfg.n_shards) for p in files.paths),
        np.int32, n)
    return {
        "uid_slot": uid_slot.astype(np.int32),
        "gid_slot": gid_slot.astype(np.int32),
        "dir_slots": dir_slots.astype(np.int32),
        "shard_id": shard_id,
        "size": files.size.astype(np.float32),
        "atime": files.atime.astype(np.float32),
        "ctime": files.ctime.astype(np.float32),
        "mtime": files.mtime.astype(np.float32),
        "uid": files.uid.astype(np.int32),
        "gid": files.gid.astype(np.int32),
        "mode": files.mode.astype(np.int32),
        "type": files.type.astype(np.int32),
        "path_hash": files.path_hash.astype(np.uint32),
    }


def split_table_by_shard(table: md.MetadataTable, n_shards: int
                         ) -> List[md.MetadataTable]:
    """Partition a scan table into per-shard sub-tables by the FNV path
    hash — the preprocessing step that feeds ``ShardedPrimaryIndex.
    ingest_tables`` (DESIGN.md §8). This is the paper's partitioned scan
    feed: the scanner (or its Kafka topic) emits one partition per index
    shard, so downstream ingest never re-routes. Row order inside a
    partition preserves scan order (stable sort)."""
    files = md.files_only(table)
    sids = files.path_hash.astype(np.uint32) % np.uint32(n_shards)
    order = np.argsort(sids, kind="stable")
    by_shard = files.select(order)
    bounds = np.searchsorted(sids[order], np.arange(n_shards + 1))
    return [by_shard.select(slice(int(bounds[s]), int(bounds[s + 1])))
            for s in range(n_shards)]


def index_columns(table: md.MetadataTable
                  ) -> Tuple[np.ndarray, Dict[str, np.ndarray]]:
    """(paths, columns) of the files-only view cast to the primary
    index's storage dtypes (``PrimaryIndex.STANDARD_COLUMNS``) — the
    canonical scan → index column view shared by snapshot ingest and the
    anti-entropy reconciler (DESIGN.md §9.1). Diffing in storage dtype
    matters: a float64 scan value that round-trips to the float32 the
    arena holds is NOT drift."""
    from repro.core.index import PrimaryIndex
    files = md.files_only(table)
    cols = {k: np.asarray(getattr(files, k), dt)
            for k, dt in PrimaryIndex.STANDARD_COLUMNS.items()}
    return files.paths, cols


def pad_rows(rows: Dict[str, np.ndarray], multiple: int) -> Tuple[Dict[str, np.ndarray], np.ndarray]:
    n = len(rows["uid_slot"])
    m = -(-n // multiple) * multiple
    valid = np.zeros(m, bool)
    valid[:n] = True
    out = {}
    for k, v in rows.items():
        pad_shape = (m - n,) + v.shape[1:]
        out[k] = np.concatenate([v, np.zeros(pad_shape, v.dtype)])
    return out, valid


# ---------------------------------------------------------------------------
# Counting pipeline (device): per-(principal, shard) object counts
# ---------------------------------------------------------------------------

def counting_local(cfg: PipelineConfig, rows: Dict, valid) -> jax.Array:
    """Reference: counts (n_principals, n_shards) float32."""
    counts = jnp.zeros((cfg.n_principals, cfg.n_shards), jnp.float32)
    w = valid.astype(jnp.float32)
    sid = rows["shard_id"]
    for pid_arr in _principal_streams(cfg, rows):
        pid, m = pid_arr
        counts = counts.at[jnp.maximum(pid, 0), sid].add(w * m)
    return counts


def _principal_streams(cfg: PipelineConfig, rows: Dict):
    yield rows["uid_slot"], jnp.ones_like(rows["uid_slot"], jnp.float32)
    yield rows["gid_slot"], jnp.ones_like(rows["gid_slot"], jnp.float32)
    ds = rows["dir_slots"]
    for li in range(ds.shape[1]):
        pid = ds[:, li]
        yield jnp.maximum(pid, 0), (pid >= 0).astype(jnp.float32)


def make_counting_step(cfg: PipelineConfig, mesh, dp_axes=("data",),
                       tp_axis="model"):
    """shard_map counting step: rows sharded over dp, principals over tp."""
    n_tp = mesh.shape[tp_axis]
    assert cfg.n_principals % n_tp == 0
    p_loc = cfg.n_principals // n_tp

    def fn(rows, valid):
        p0 = jax.lax.axis_index(tp_axis) * p_loc
        counts = jnp.zeros((p_loc, cfg.n_shards), jnp.float32)
        w = valid.astype(jnp.float32)
        sid = rows["shard_id"]
        for pid, m in _principal_streams(cfg, rows):
            lp = pid - p0
            sel = (lp >= 0) & (lp < p_loc)
            counts = counts.at[jnp.clip(lp, 0, p_loc - 1), sid].add(
                w * m * sel.astype(jnp.float32))
        return jax.lax.psum(counts, dp_axes)

    row_spec = {k: P(dp_axes, *([None] * (v - 1)))
                for k, v in {"uid_slot": 1, "gid_slot": 1, "dir_slots": 2,
                             "shard_id": 1, "size": 1, "atime": 1, "ctime": 1,
                             "mtime": 1, "uid": 1, "gid": 1, "mode": 1,
                             "type": 1, "path_hash": 1}.items()}
    return shard_map(fn, mesh=mesh,
                     in_specs=(row_spec, P(dp_axes)),
                     out_specs=P(tp_axis, None), check_vma=False)


# ---------------------------------------------------------------------------
# Aggregate pipeline (device): grouped DDSketch per principal x attribute
# ---------------------------------------------------------------------------

def aggregate_local(cfg: PipelineConfig, rows: Dict, valid) -> Dict:
    """Reference: full sketch state dict with leading (n_principals, 4)."""
    state = dds.init(cfg.sketch, (cfg.n_principals, len(ATTRS)))
    for ai, attr in enumerate(ATTRS):
        vals = rows[attr]
        for pid, m in _principal_streams(cfg, rows):
            sub = jax.tree.map(lambda s: s[:, ai], state)
            sub = dds.update_grouped(cfg.sketch, sub, vals, pid,
                                     cfg.n_principals,
                                     mask=m * valid.astype(jnp.float32))
            state = jax.tree.map(lambda s, ns: s.at[:, ai].set(ns), state, sub)
    return state


def make_aggregate_step(cfg: PipelineConfig, mesh, dp_axes=("data",),
                        tp_axis="model", scatter_merge: bool = False):
    """Grouped DDSketch per principal x attribute through the DDSketch
    kernel's entry point (compiled on a TPU, its jnp reference on the
    CPU): one update per attribute, over all principal streams stacked
    as (streams, N). scatter_merge: reduce-scatter the sketch merge over
    the DP axes (halves merge wire bytes; output principals shard over
    tp x dp)."""
    n_tp = mesh.shape[tp_axis]
    assert cfg.n_principals % n_tp == 0
    p_loc = cfg.n_principals // n_tp
    n_dp = 1
    for a in dp_axes:
        n_dp *= mesh.shape[a]
    if scatter_merge:
        assert p_loc % n_dp == 0, (p_loc, n_dp)

    def fn(rows, valid):
        p0 = jax.lax.axis_index(tp_axis) * p_loc
        state = dds.init(cfg.sketch, (p_loc, len(ATTRS)))
        vmask = valid.astype(jnp.float32)
        lps, masks = [], []
        for pid, m in _principal_streams(cfg, rows):
            lp = pid - p0
            sel = ((lp >= 0) & (lp < p_loc)).astype(jnp.float32)
            lps.append(jnp.clip(lp, 0, p_loc - 1))
            masks.append(m * sel * vmask)
        lps, masks = jnp.stack(lps), jnp.stack(masks)     # (streams, N)
        for ai, attr in enumerate(ATTRS):
            sub = jax.tree.map(lambda s: s[:, ai], state)
            sub = dd_ops.update_grouped(cfg.sketch, sub, rows[attr], lps,
                                        p_loc, mask=masks)
            state = jax.tree.map(lambda s, ns: s.at[:, ai].set(ns), state, sub)
        if scatter_merge:
            return dds.merge_psum_scatter(state, dp_axes)
        return dds.merge_psum(state, dp_axes)

    row_spec = {k: P(dp_axes, *([None] * (v - 1)))
                for k, v in {"uid_slot": 1, "gid_slot": 1, "dir_slots": 2,
                             "shard_id": 1, "size": 1, "atime": 1, "ctime": 1,
                             "mtime": 1, "uid": 1, "gid": 1, "mode": 1,
                             "type": 1, "path_hash": 1}.items()}
    p_axes = (tp_axis,) + tuple(dp_axes) if scatter_merge else (tp_axis,)
    state_spec = {
        "counts": P(p_axes, None, None),
        "zero_count": P(p_axes, None),
        "count": P(p_axes, None),
        "total": P(p_axes, None),
        "min": P(p_axes, None),
        "max": P(p_axes, None),
    }
    return shard_map(fn, mesh=mesh,
                     in_specs=(row_spec, P(dp_axes)),
                     out_specs=state_spec, check_vma=False)


# ---------------------------------------------------------------------------
# Primary pipeline (host assembles records; device computes shard ids)
# ---------------------------------------------------------------------------

def primary_records(table: md.MetadataTable, cfg: PipelineConfig,
                    version: int = 1, visible_to: str = "admin"):
    """Yield Globus-Search-style record batches (~batch_bytes each)."""
    files = md.files_only(table)
    batch: List[Dict] = []
    size = 0
    for i in range(len(files)):
        rec = {
            "subject": files.paths[i],
            "visible_to": [visible_to, f"user:{int(files.uid[i])}"],
            "content": {
                "type": "f" if files.type[i] == md.TYPE_FILE else "l",
                "mode": int(files.mode[i]),
                "uid": int(files.uid[i]),
                "gid": int(files.gid[i]),
                "size": float(files.size[i]),
                "atime": float(files.atime[i]),
                "ctime": float(files.ctime[i]),
                "mtime": float(files.mtime[i]),
                "version": version,
            },
        }
        b = len(json.dumps(rec))
        if size + b > cfg.batch_bytes and batch:
            yield batch
            batch, size = [], 0
        batch.append(rec)
        size += b
    if batch:
        yield batch


# ---------------------------------------------------------------------------
# Post-processing (host script, as in the paper): recursive dir counts
# ---------------------------------------------------------------------------

def recursive_dir_counts(nonrec: np.ndarray, parent: np.ndarray,
                         depth: np.ndarray) -> np.ndarray:
    """nonrec: (n_dirs,) per-directory non-recursive counts; parent/depth:
    directory tree arrays. Returns recursive totals (children fold into
    parents, deepest first)."""
    rec = nonrec.astype(np.float64).copy()
    order = np.argsort(-depth.astype(np.int64), kind="stable")
    for i in order:
        p = parent[i]
        if p >= 0:
            rec[p] += rec[i]
    return rec
