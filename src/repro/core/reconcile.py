"""Anti-entropy reconciliation + tombstone compaction (DESIGN.md §9).

Icicle's unified view is maintained by BOTH ingestion paths: periodic
snapshot scans and real-time changelog events (paper §II, §IV). The
snapshot path exists precisely to repair drift when events are dropped
or a feed lags — Robinhood likewise falls back to periodic namespace
scans to resync its changelog-derived database (arXiv:1505.01448). This
module closes that loop, plus the arena-hygiene problem that makes
long-lived indexes slow:

- **reconcile(table, version, ...)** — anti-entropy pass: diff a fresh
  ``MetadataTable`` scan against the live index *per shard* (split by
  the same FNV routing family every ingest path uses, so each shard is
  diffed against exactly the rows it owns) and emit synthetic
  create/update/delete repair batches through the event ingestor's
  apply path (``EventIngestor.apply_repairs``) under the shared logical
  clock. A lossy event feed converges to the snapshot's state WITHOUT a
  from-scratch rebuild: only drifted rows are written, and the ``>=``
  version gate protects records the live feed touched after the scan.
  The watermark gains a ``reconciled_at`` mark surfaced by
  ``QueryEngine`` / ``MonitorPool`` freshness.

- **compact_if_needed(primary, ...)** — tombstone compaction: normal
  ingest never reclaims tombstoned slots, so every ``live()`` scan pays
  for all-time deletes. When a shard's dead-slot fraction crosses the
  threshold, its arenas are rewritten to live-only rows
  (``PrimaryIndex.compact``: contiguous-slice fast path, slot map
  rebuilt through the pluggable SlotMap protocol, versions kept) and
  the principals the dead rows touched are republished out of the
  aggregate index with exact counts (zero-count ghosts dropped).

Discovery-index interaction (DESIGN.md §11.3): repair batches flow
through the same primary mutations an event batch uses, so an attached
``discovery.ShardDiscovery`` absorbs them as ordinary deltas and stays
fresh across a reconcile; compaction renumbers slots, so
``PrimaryIndex.compact`` invalidates and rebuilds the attached
discovery state from the surviving live rows — a compacted shard keeps
accelerating without any caller involvement.

``tests/test_reconcile.py`` pins both as exactness contracts: a
reconciled index equals a from-scratch rebuild, and compaction leaves
every Table-I answer unchanged.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import numpy as np

from repro.core import metadata as md
from repro.core import snapshot as snap
from repro.core.index import PrimaryIndex

#: default dead-slot fraction above which an arena is worth rewriting
#: (compaction is O(live rows); below ~30% dead the scan tax is smaller
#: than the rewrite)
COMPACT_THRESHOLD = 0.30


@dataclasses.dataclass
class ReconcileReport:
    """What one anti-entropy pass found and did.

    ``creates``/``updates``/``deletes`` count DIFFS (snapshot subjects
    missing or tombstoned in the index / live subjects with drifted
    columns / live subjects absent from the snapshot).
    ``applied_upserts`` counts upsert repairs SUBMITTED (the batch ops
    version-gate stale ones internally, invisibly to the caller);
    ``applied_tombstones`` counts deletes that actually landed — a diff
    whose record the live feed superseded after the scan loses the
    version race by design.
    """

    version: int = 0
    checked: int = 0
    creates: int = 0
    updates: int = 0
    deletes: int = 0
    applied_upserts: int = 0
    applied_tombstones: int = 0
    shards: int = 0
    reclaimed_slots: int = 0

    @property
    def repairs(self) -> int:
        return self.creates + self.updates + self.deletes


def diff_shard(shard: PrimaryIndex, paths: np.ndarray,
               cols: Dict[str, np.ndarray],
               hashes: Optional[np.ndarray] = None
               ) -> Tuple[np.ndarray, int, np.ndarray, np.ndarray,
                          np.ndarray, np.ndarray]:
    """Diff one shard's arenas against the snapshot rows it owns.

    Returns ``(up_rows, n_creates, del_paths, del_uid, del_gid,
    del_hashes)``: ``up_rows`` indexes into ``paths`` — subjects
    needing an upsert repair (missing, tombstoned, or column-drifted);
    the ``del_*`` arrays describe the shard's live slots no snapshot
    row claimed (mark-and-sweep — no string set-membership pass): their
    subjects, stored owners for the counting decrement, and stored FNV
    hashes so the repair tombstones route without re-hashing.

    Drift detection compares every snapshot column against the stored
    arena value in storage dtype, exactly — byte-identity with a
    from-scratch rebuild is the contract the differential oracle pins.
    Columns the shard never materialized compare as zeros (the
    schema-stable ``live()`` rule).
    """
    n_rows = len(paths)
    n_idx = len(shard.slot_map)
    slots = (shard.slot_map.lookup(paths, hashes) if n_rows
             else np.zeros(0, np.int64))
    known = slots >= 0
    s = np.clip(slots, 0, None)
    alive = np.zeros(n_rows, bool)
    if n_idx:
        alive[known] = shard.alive[s[known]]
    drift = np.zeros(n_rows, bool)
    if n_idx:        # no slots -> nothing alive, nothing to compare
        for k, v in cols.items():
            stored = shard.columns.get(k)
            if stored is None:
                drift |= alive & (v != 0)
            else:
                drift |= alive & (stored[s] != v)
    up_rows = np.nonzero(~alive | drift)[0]
    # mark-and-sweep: live slots unclaimed by any snapshot row are gone
    hit = np.zeros(n_idx, bool)
    hit[s[known]] = True
    del_slots = np.nonzero(shard.alive[:n_idx] & ~hit)[0]
    del_paths = shard.paths[del_slots]

    def col_of(key, dt):
        col = shard.columns.get(key)
        return (col[del_slots] if col is not None
                else np.zeros(len(del_slots), dt))

    del_uid = col_of("uid", np.int32)
    del_gid = col_of("gid", np.int32)
    del_hashes = col_of("path_hash", np.uint32)
    n_creates = int((~alive).sum())
    return up_rows, n_creates, del_paths, del_uid, del_gid, del_hashes


def reconcile(table: md.MetadataTable, version: int,
              primary=None, ingestor=None,
              compact_threshold: Optional[float] = None) -> ReconcileReport:
    """Anti-entropy pass: converge the live index to a fresh snapshot.

    ``table`` is the scan, ``version`` the changelog seq at scan time
    (the shared logical clock — same convention as ``ingest_table``).
    Give EITHER ``ingestor`` (repairs route through
    ``EventIngestor.apply_repairs``: watermark + aggregate deltas +
    ``reconciled_at``; ``primary`` defaults to the ingestor's) or a bare
    ``primary`` (repairs hit the index's batch mutations directly —
    snapshot-only deployments).

    The diff runs per shard via the FNV routing family; the repair
    batches re-route through the index's normal batch mutations, so
    every write meets the records it repairs in the owning shard. The
    diff may over-emit against a concurrently-advancing feed (it does
    not inspect versions); the ``>=`` gate at apply time drops exactly
    the stale repairs, which is what makes reconciling safe to race
    with live ingestion.

    ``compact_threshold`` optionally chains a compaction pass after the
    repairs (reconcile deletes create tombstones; a drifted index often
    crosses the threshold right here). None skips it.

    Scope: reconcile repairs the INDEX, not the event state manager's
    fid -> (parent, name) tables — a dropped CREAT still leaves later
    events on that fid resolving through the ``#fid`` fallback
    (counted loudly in ``metrics["unresolved"]``) until the next pass
    sweeps the junk subject, or a ``register_tree`` handoff from a
    fid-bearing scan refreshes the tree. Deployments whose scanner
    records fids should pair the two, exactly as snapshot ingest does.
    """
    if ingestor is not None:
        if primary is None:
            primary = ingestor.primary
        ingestor.flush()        # diff against the applied state
    assert primary is not None, "need a primary index or an ingestor"
    paths, cols = snap.index_columns(table)
    hashes = cols["path_hash"]
    report = ReconcileReport(version=version, checked=len(paths))

    up_rows_g, dels_g = [], []
    if hasattr(primary, "shards"):
        # one routing definition: the index's own route + stable split
        _, sids = primary.route(paths, hashes)
        order, bounds = primary._order_split(sids)
        for si, shard in enumerate(primary.shards):
            rows = order[int(bounds[si]):int(bounds[si + 1])]
            up, n_new, *dels = diff_shard(
                shard, paths[rows], {k: v[rows] for k, v in cols.items()},
                hashes[rows])
            up_rows_g.append(rows[up])
            dels_g.append(dels)
            report.creates += n_new
            report.updates += len(up) - n_new
            report.shards += 1
    else:
        up, n_new, *dels = diff_shard(primary, paths, cols, hashes)
        up_rows_g.append(up)
        dels_g.append(dels)
        report.creates += n_new
        report.updates += len(up) - n_new
        report.shards = 1

    up_rows = np.concatenate(up_rows_g)
    del_paths, del_uid, del_gid, del_hashes = (
        np.concatenate(parts) for parts in zip(*dels_g))
    report.deletes = len(del_paths)
    up_paths = paths[up_rows]
    up_fields = {k: v[up_rows] for k, v in cols.items()}

    if ingestor is not None:
        res = ingestor.apply_repairs(up_paths, up_fields, del_paths,
                                     del_uid, del_gid, version,
                                     del_hashes=del_hashes)
        report.applied_upserts = res["upserts"]
        report.applied_tombstones = res["tombstones"]
    else:
        vers = np.full(len(up_paths), version, np.int64)
        primary.upsert_batch(up_paths, up_fields, vers)
        del_mask = primary.delete_batch(
            del_paths, np.full(len(del_paths), version, np.int64),
            hashes=del_hashes)
        report.applied_upserts = len(up_paths)
        report.applied_tombstones = int(np.asarray(del_mask).sum())

    if compact_threshold is not None:
        report.reclaimed_slots = compact_if_needed(
            primary, threshold=compact_threshold, ingestor=ingestor)
    return report


def compact_if_needed(primary, threshold: float = COMPACT_THRESHOLD,
                      ingestor=None) -> int:
    """Compact every arena whose dead-slot fraction exceeds
    ``threshold`` (DESIGN.md §9.2). Works on a monolithic
    ``PrimaryIndex`` or per shard on a ``ShardedPrimaryIndex`` (each
    shard decides independently — hot-churn partitions rewrite, cold
    ones don't). With an ``ingestor`` attached, the principals the
    reclaimed tombstones touched are republished from sketch state with
    exact counts, dropping zero-count ghosts from the aggregate index
    (``from_sketch_state(only=...)``). Returns total slots reclaimed.

    Compaction changes NO observable state: the live set, column
    values, surviving versions, and the watermark are all preserved
    (the differential suite pins this) — only scan cost drops.
    """
    if ingestor is None or not ingestor.cfg.update_aggregates:
        # no aggregate side to maintain: the index's own compaction
        # API already applies the per-shard threshold rule
        if hasattr(primary, "shards"):
            return primary.compact(threshold=threshold)
        st = primary.slot_stats()
        return (primary.compact() if st["dead"]
                and st["dead_fraction"] > threshold else 0)

    shards = primary.shards if hasattr(primary, "shards") else [primary]
    factory = getattr(primary, "slot_map_factory", None)
    reclaimed = 0
    dead_pids: set = set()
    for sh in shards:
        st = sh.slot_stats()
        if not st["dead"] or st["dead_fraction"] <= threshold:
            continue
        n = len(sh.slot_map)
        dead_slots = np.nonzero(~sh.alive[:n])[0]
        uid = sh.columns.get("uid")
        gid = sh.columns.get("gid")
        dead_pids |= ingestor.principals_of(
            list(sh.paths[dead_slots]),
            uid[dead_slots] if uid is not None
            else np.zeros(len(dead_slots), np.int32),
            gid[dead_slots] if gid is not None
            else np.zeros(len(dead_slots), np.int32))
        reclaimed += sh.compact(slot_map_factory=factory)
    if reclaimed:
        ingestor.republish(dead_pids)
    return reclaimed
