"""Hash-partitioned primary index with scatter-gather access (DESIGN.md §8).

The paper's core claim is *horizontally scalable* ingestion and query;
the monolithic ``PrimaryIndex`` serializes both behind one flat arena
and one per-row Python dict sweep. ``ShardedPrimaryIndex`` partitions
records across N ``PrimaryIndex`` shards by path hash:

- **routing** uses the repo's one FNV-1a hash family
  (``metadata.path_hash`` == the ``kernels/hashshard`` op): batches
  route through precomputed hash columns (``table.path_hash``, the
  event path's ``fields["path_hash"]``) or the hashshard device op on
  raw paths; singletons fall back to ``metadata.path_hash`` on the host.
  One family everywhere means a record's shard is a pure function of its
  subject, so snapshot ingest, event upserts, and tombstones for the
  same path always meet in the same shard.
- **ingest** splits each batch into per-shard contiguous runs with one
  stable sort (relative order preserved inside a shard, so the event
  path's seq-ascending contract survives) and applies per-shard
  vectorized mutations. Each shard runs a ``HashSlotMap`` —
  subject->slot assignment through C-speed khash batch probes (exact
  string keys) instead of the monolith's per-row Python dict sweep.
- **queries** scatter-gather: point lookups route to one shard (one
  slot-map probe), scans fan out per shard and merge a schema-stable
  ``live()`` view.
- **rename migration**: a repath that moves a record between shards is
  already a delete+upsert pair at the event layer (old subject
  tombstone + new subject upsert), and each half routes independently —
  so cross-shard migration needs no extra machinery, only the shared
  hash family. The global watermark/version clock is untouched: shards
  hold record versions, the ingestor holds the single watermark.

``tests/test_sharded_index.py`` pins the sharded layout's answers to
the monolith's.
"""
from __future__ import annotations

import threading
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core import metadata as md
from repro.core.index import PrimaryIndex, _locked
from repro.core.telemetry import resolve as _resolve_tel

# modular inverse of the FNV prime mod 2^32: lets the vectorized hash
# process fixed-width zero-padded rows unmasked (a trailing zero byte
# only multiplies: (h ^ 0) * p) and then undo the padding afterwards
FNV_PRIME_INV = pow(md.FNV_PRIME, -1, 1 << 32)


def path_hashes(paths: Sequence[str]) -> np.ndarray:
    """Vectorized ``metadata.path_hash`` over a batch: paths pack into a
    fixed-width byte matrix (the hashshard kernel's input layout), the
    FNV-1a recurrence runs across rows one byte-column at a time, and
    the zero-padding is divided back out via the prime's modular
    inverse. Exactly equal to ``md.path_hash`` per element; falls back
    to the scalar loop for non-ASCII batches."""
    n = len(paths)
    if n == 0:
        return np.zeros(0, np.uint32)
    try:
        b = np.array(paths if isinstance(paths, list) else list(paths),
                     dtype=np.bytes_)
    except UnicodeEncodeError:
        return np.fromiter((md.path_hash(p) for p in paths), np.uint32, n)
    w = b.dtype.itemsize
    lens = np.char.str_len(b).astype(np.int64)
    mat_t = np.ascontiguousarray(
        b.view(np.uint8).reshape(n, w).T).astype(np.uint32)
    h = np.full(n, md.FNV_OFFSET, np.uint32)
    prime = np.uint32(md.FNV_PRIME)
    for i in range(w):
        np.bitwise_xor(h, mat_t[i], out=h)
        np.multiply(h, prime, out=h)
    pw = np.full(w + 1, FNV_PRIME_INV & 0xFFFFFFFF, np.uint32)
    pw[0] = 1
    # pinv^k mod 2^32 (pin the dtype: accumulate upcasts uints by default)
    pw = np.multiply.accumulate(pw, dtype=np.uint32)
    return (h * pw[w - lens]).astype(np.uint32)


try:                                     # baked into the CI/dev image;
    import pandas as _pd                 # the sharded index degrades to
except ImportError:                      # the dict slot map without it
    _pd = None


class HashSlotMap:
    """Subject -> slot map with C-speed batch operations — the per-shard
    replacement for ``index.DictSlotMap``'s per-row Python sweep.

    Two tiers, both exact on full path strings (no hash-collision
    identity games):

    - a **base index** (pandas ``Index`` over object strings — a khash
      table probed in C via ``get_indexer``; CPython caches each str's
      hash, so warm probes are pointer-cheap), position == slot id;
    - a small **overlay** dict absorbing incremental inserts (event
      micro-batches). When the overlay outgrows
      ``max(rebuild_min, len(base) >> 2)`` it folds into the base —
      O(total) concat, amortized geometrically like arena growth.

    Batches against an empty map take the ``factorize`` fast path (one
    C pass: dedup + first-occurrence codes — exactly DictSlotMap's slot
    numbering). Sharding keeps each base small, so fold-ins and hash
    builds touch 1/N of the namespace.
    """

    def __init__(self, rebuild_min: int = 8192):
        self._base = None                # pd.Index | None
        self._overlay: Dict[str, int] = {}
        self._olist: List[str] = []      # overlay subjects, slot order
        self._rebuild_min = rebuild_min
        self._probe = None               # engine-direct get_indexer
        if _pd is None:
            raise ImportError(
                "HashSlotMap needs pandas; use index.DictSlotMap")

    def __len__(self) -> int:
        return (0 if self._base is None else len(self._base)) \
            + len(self._olist)

    def _nbase(self) -> int:
        return 0 if self._base is None else len(self._base)

    def _fold_overlay(self) -> None:
        # geometric growth (1.25x) bounds total fold work at O(K)
        # amortized while keeping the python-probed overlay small
        if len(self._olist) <= max(self._rebuild_min, self._nbase() >> 2):
            return
        ov = _pd.Index(np.asarray(self._olist, object))
        self._base = ov if self._base is None else self._base.append(ov)
        self._overlay = {}
        self._olist = []
        self._probe = None

    def _base_probe(self, paths_arr: np.ndarray) -> np.ndarray:
        """get_indexer against the base, engine-direct when available:
        the public path wraps every target in an Index (a dtype-inference
        pass per call) — measurable at event-micro-batch rates."""
        if self._probe is None:
            try:
                eng = self._base._engine
                probe = eng.get_indexer
                got = probe(paths_arr[:1])       # validate private API
                want = self._base.get_indexer(paths_arr[:1])
                assert np.array_equal(got, want)
                self._probe = probe
            except Exception:
                self._probe = self._base.get_indexer
        return np.asarray(self._probe(paths_arr), np.int64)

    # -- scalar protocol ------------------------------------------------------

    def get(self, path: str) -> Optional[int]:
        got = self._overlay.get(path)
        if got is not None:
            return got
        if self._base is not None:
            loc = self._base_probe(np.array([path], object))[0]
            if loc >= 0:
                return int(loc)
        return None

    def get_or_add(self, path: str) -> Tuple[int, bool]:
        slot = self.get(path)
        if slot is not None:
            return slot, False
        slot = len(self)
        self._overlay[path] = slot
        self._olist.append(path)
        self._fold_overlay()
        return slot, True

    # -- batch protocol -------------------------------------------------------

    def assign(self, paths: Sequence[str],
               hashes: Optional[np.ndarray] = None
               ) -> Tuple[np.ndarray, np.ndarray]:
        """(slots, new_mask): slots for every row, inserting unseen
        subjects — DictSlotMap.assign semantics (duplicates share the
        first occurrence's slot; ``new_mask`` flags first occurrences of
        new subjects). ``hashes`` is accepted for slot-map protocol
        parity; exactness comes from string keys."""
        n = len(paths)
        paths_arr = (paths if isinstance(paths, np.ndarray)
                     else np.asarray(paths, object))
        if self._base is None and not self._overlay:
            codes, uniques = _pd.factorize(paths_arr)
            self._base = _pd.Index(uniques)
            self._probe = None
            new_mask = np.zeros(n, bool)
            _, first = np.unique(codes, return_index=True)
            new_mask[first] = True
            return codes.astype(np.int64), new_mask
        slots = self._lookup_arr(paths_arr)
        new_mask = np.zeros(n, bool)
        miss = slots < 0
        if miss.any():
            mi = np.nonzero(miss)[0]
            codes, uniques = _pd.factorize(paths_arr[mi])
            base = len(self)
            self._overlay.update(
                zip(uniques, range(base, base + len(uniques))))
            self._olist.extend(uniques)
            slots[mi] = base + codes
            _, first = np.unique(codes, return_index=True)
            new_mask[mi[first]] = True
            self._fold_overlay()
        return slots, new_mask

    def _lookup_arr(self, paths_arr: np.ndarray) -> np.ndarray:
        if self._base is not None:
            slots = self._base_probe(paths_arr)
        else:
            slots = np.full(len(paths_arr), -1, np.int64)
        if self._overlay:
            miss = np.nonzero(slots < 0)[0]
            if len(miss):
                got = list(map(self._overlay.get, paths_arr[miss]))  # C pass
                slots[miss] = np.fromiter(
                    (-1 if g is None else g for g in got),
                    np.int64, len(got))
        return slots

    def lookup(self, paths: Sequence[str],
               hashes: Optional[np.ndarray] = None) -> np.ndarray:
        """Slots for known subjects, -1 for unknown; no insertion."""
        paths_arr = (paths if isinstance(paths, np.ndarray)
                     else np.asarray(paths, object))
        return self._lookup_arr(paths_arr)


def shard_of(path: str, n_shards: int) -> int:
    """Host-fallback singleton routing: the FNV family mod shard count."""
    return md.path_hash(path) % n_shards


class ShardedPrimaryIndex:
    """N hash-partitioned ``PrimaryIndex`` shards behind the monolith's
    mutation/read protocol (see module docstring).

    ``kernel_route_min``: raw-path batches at least this large route
    through the hashshard device op (``kernels/hashshard``); smaller
    batches and singletons use the host fallback. Batches that already
    carry the hash column skip both.
    """

    def __init__(self, n_shards: int = 4, kernel_route_min: int = 4096,
                 route_width: int = 192, slot_map_factory=None,
                 telemetry=None):
        assert n_shards >= 1
        if slot_map_factory is None:
            from repro.core.index import DictSlotMap
            slot_map_factory = (HashSlotMap if _pd is not None
                                else DictSlotMap)
        self.n_shards = n_shards
        self.kernel_route_min = kernel_route_min
        self.route_width = route_width
        self.slot_map_factory = slot_map_factory
        self.telemetry = _resolve_tel(telemetry)
        self.shards: List[PrimaryIndex] = [
            PrimaryIndex(slot_map=slot_map_factory(),
                         telemetry=self.telemetry)
            for _ in range(n_shards)]
        self.rollups = None
        # stage spans of route and upsert_batch, bound once; each run
        # per batch, on the host, outside every jitted function
        self._span_encode = self.telemetry.span("index.route.encode")
        self._span_device = self.telemetry.span("index.route.device")
        self._span_split = self.telemetry.span("index.upsert.split")
        # per-shard routed-record counters, bound once: the mutation
        # loops run per shard already, so the only extra cost per apply
        # is one inc per non-empty shard slice
        fam = self.telemetry.counter(
            "shard_mutation_records_total",
            "records routed to each shard by mutation kind",
            labels=("shard", "op"))
        self._c_ingest = [fam.labels(str(s), "ingest")
                          for s in range(n_shards)]
        self._c_upsert = [fam.labels(str(s), "upsert")
                          for s in range(n_shards)]
        self._c_delete = [fam.labels(str(s), "delete")
                          for s in range(n_shards)]
        self._route_rows = self.telemetry.counter(
            "index_route_rows_total",
            "rows routed on the device by the width bucket of their batch",
            labels=("width",))
        self._bind_route_rows()
        # top-level MVCC write lock (DESIGN.md §12): cross-shard
        # mutations and snapshot pinning serialize here, then take the
        # per-shard locks inside — one consistent order, no deadlock
        self._lock = threading.RLock()

    # -- routing --------------------------------------------------------------

    def _bind_route_rows(self) -> None:
        from repro.kernels.hashshard.ref import width_buckets
        self._c_route_rows = {w: self._route_rows.labels(str(w))
                              for w in width_buckets(self.route_width)}

    def shard_of(self, path: str) -> int:
        return shard_of(path, self.n_shards)

    def route(self, paths: Sequence[str],
              hashes: Optional[np.ndarray] = None
              ) -> Tuple[np.ndarray, np.ndarray]:
        """(hashes, shard_ids) for a batch. Precomputed hashes win;
        otherwise large batches go through the hashshard device op and
        small ones through the vectorized host fallback."""
        n = len(paths)
        if hashes is not None:
            h = np.asarray(hashes, np.uint32)
        elif n >= self.kernel_route_min:
            h = self._route_device(paths)
        else:
            h = path_hashes(paths)
        return h, (h % np.uint32(self.n_shards)).astype(np.int64)

    def _route_device(self, paths: Sequence[str]) -> np.ndarray:
        """Batch routing through the hashshard op (paper's crc32-shard
        analogue, §IV-A2). Rows are cut at the batch's own width bucket
        (``encode_strings_joined``: the longest path's power of two, at
        least 32, at most ``route_width``). Rows longer than
        ``route_width`` cannot be width-truncated without desyncing from
        the host fallback — they are patched via ``md.path_hash``."""
        from repro.core.index import bucket_pow2
        from repro.kernels.hashshard import ops as hs_ops
        from repro.kernels.hashshard.ref import encode_strings_joined
        n = len(paths)
        with self._span_encode:
            rows, lens, truncated = encode_strings_joined(paths,
                                                          self.route_width)
        self._c_route_rows[rows.shape[1]].inc(n)
        with self._span_device:           # H2D, kernel, readback
            pad = bucket_pow2(n) - n      # O(log N) jit shape universe
            if pad:
                rows = np.pad(rows, ((0, pad), (0, 0)))
                lens = np.pad(lens, (0, pad))
            h, _ = hs_ops.hashshard_route(rows, lens, self.n_shards)
            h = np.asarray(h[:n], np.uint32).copy()
        for i in np.nonzero(truncated)[0]:
            h[i] = md.path_hash(paths[i])
        return h

    def _order_split(self, sids: np.ndarray):
        """(order, bounds): one stable sort groups a batch into per-shard
        contiguous runs — rows keep their relative order inside a shard
        (the seq-ascending contract), and splitting costs one gather per
        array instead of n_shards boolean passes."""
        order = np.argsort(sids, kind="stable")
        bounds = np.searchsorted(sids[order], np.arange(self.n_shards + 1))
        return order, bounds

    # -- MVCC snapshot views (DESIGN.md §12) ----------------------------------

    def write_lock(self):
        """The top-level reentrant lock serializing cross-shard
        mutations against snapshot pinning (see ``PrimaryIndex.
        write_lock``; composite writers hold it across a whole apply)."""
        return self._lock

    def snapshot(self, freshness: Optional[Dict] = None):
        """Pin a read-only MVCC view: one per-shard pin taken under the
        top-level lock, so the shard views are mutually consistent
        (every cross-shard mutation runs under the same lock). Returns
        a ``mvcc.ShardedIndexSnapshot`` — close it to release."""
        from repro.core.mvcc import ShardedIndexSnapshot
        with self._lock:
            return ShardedIndexSnapshot(
                self, [sh.snapshot() for sh in self.shards],
                freshness=freshness)

    def snapshot_stats(self) -> Dict[str, int]:
        """Per-shard pin accounting summed: a sharded view holds one
        pin per shard, so ``open_snapshots`` counts views x shards
        (0 still means "no pins anywhere" for the leak check)."""
        with self._lock:
            per = [sh.snapshot_stats() for sh in self.shards]
        return {"open_snapshots": sum(p["open_snapshots"] for p in per),
                "pinned_epochs": sum(p["pinned_epochs"] for p in per)}

    # -- mutations (monolith protocol) ----------------------------------------

    @_locked
    def ingest_table(self, table: md.MetadataTable, version: int) -> int:
        """Snapshot ingest: split the (preprocessed) table per shard on
        its own ``path_hash`` column, then bulk-ingest each slice. The
        split converts to device dtypes ONCE, permutes by one stable
        sort, and hands each shard zero-copy views (``ingest_columns``)
        — no per-shard sub-table materialization. ``invalidate_older``
        runs on every shard — also the ones this snapshot assigned no
        rows — so absence still tombstones."""
        files = md.files_only(table)
        ph = files.path_hash.astype(np.uint32)
        sids = ph % np.uint32(self.n_shards)
        order, bounds = self._order_split(sids)
        # raw column views; the per-shard write fuses gather + device-
        # dtype cast + arena store into one pass per column
        cols = {k: getattr(files, k)
                for k in PrimaryIndex.STANDARD_COLUMNS}
        n_new = 0
        for s in range(self.n_shards):
            lo, hi = int(bounds[s]), int(bounds[s + 1])
            if lo == hi:
                self.shards[s].invalidate_older(version)
            else:
                rows = order[lo:hi]
                n_new += self.shards[s].ingest_columns(
                    files.paths[rows], cols, version, rows=rows,
                    hashes=ph[rows])
                self._c_ingest[s].inc(hi - lo)
        return n_new

    @_locked
    def ingest_tables(self, tables: Sequence[md.MetadataTable],
                      version: int) -> int:
        """Ingest pre-partitioned sub-tables (``snapshot.
        split_table_by_shard`` — the paper's preprocessed, partitioned
        scan feed): sub-table i goes straight to shard i, no routing or
        splitting on this path. Shards whose sub-table is empty still
        ``invalidate_older`` so absence tombstones."""
        assert len(tables) == self.n_shards
        n_new = 0
        for shard, sub in zip(self.shards, tables):
            if len(sub):
                n_new += shard.ingest_table(sub, version)
            else:
                shard.invalidate_older(version)
        return n_new

    @_locked
    def upsert(self, path: str, fields: Dict, version: int) -> None:
        self.shards[self.shard_of(path)].upsert(path, fields, version)

    @_locked
    def delete(self, path: str, version: int) -> None:
        self.shards[self.shard_of(path)].delete(path, version)

    @_locked
    def upsert_batch(self, paths: Sequence[str],
                     fields: Dict[str, np.ndarray],
                     versions: np.ndarray,
                     hashes: Optional[np.ndarray] = None) -> np.ndarray:
        """Scatter a coalesced upsert batch across shards. Routing reuses
        ``fields["path_hash"]`` when the caller (the event ingestor)
        already computed it. The stable order-split preserves relative
        order inside a shard, so the duplicate-subjects-seq-ascending
        contract of the monolith holds per shard."""
        n = len(paths)
        if n == 0:
            return np.zeros(0, bool)
        if hashes is None and "path_hash" in fields:
            hashes = np.asarray(fields["path_hash"], np.uint32)
        if hashes is None:                # hashing has its own spans
            hashes, _ = self.route(paths)
        with self._span_split:
            h, sids = self.route(paths, hashes)
            paths_arr = (paths if isinstance(paths, np.ndarray)
                         else np.asarray(paths, object))
            versions = np.broadcast_to(np.asarray(versions, np.int64), (n,))
            order, bounds = self._order_split(sids)
            paths_o = paths_arr[order]
            vers_o = versions[order]
            h_o = h[order]
            fields_o = {k: np.asarray(v)[order] for k, v in fields.items()}
        out = np.zeros(n, bool)
        for s in range(self.n_shards):
            lo, hi = int(bounds[s]), int(bounds[s + 1])
            if lo == hi:
                continue
            out[order[lo:hi]] = self.shards[s].upsert_batch(
                paths_o[lo:hi],
                {k: v[lo:hi] for k, v in fields_o.items()},
                vers_o[lo:hi], hashes=h_o[lo:hi])
            self._c_upsert[s].inc(hi - lo)
        return out

    @_locked
    def delete_batch(self, paths: Sequence[str], versions: np.ndarray,
                     hashes: Optional[np.ndarray] = None) -> np.ndarray:
        n = len(paths)
        if n == 0:
            return np.zeros(0, bool)
        h, sids = self.route(paths, hashes)
        paths_arr = (paths if isinstance(paths, np.ndarray)
                     else np.asarray(paths, object))
        versions = np.broadcast_to(np.asarray(versions, np.int64), (n,))
        order, bounds = self._order_split(sids)
        paths_o = paths_arr[order]
        vers_o = versions[order]
        h_o = h[order]
        out = np.zeros(n, bool)
        for s in range(self.n_shards):
            lo, hi = int(bounds[s]), int(bounds[s + 1])
            if lo == hi:
                continue
            out[order[lo:hi]] = self.shards[s].delete_batch(
                paths_o[lo:hi], vers_o[lo:hi], hashes=h_o[lo:hi])
            self._c_delete[s].inc(hi - lo)
        return out

    @_locked
    def invalidate_older(self, version: int) -> int:
        return sum(sh.invalidate_older(version) for sh in self.shards)

    # -- discovery (secondary indexes; DESIGN.md §11) -------------------------

    @_locked
    def attach_discovery(self, cfg=None) -> List:
        """Attach one discovery.ShardDiscovery per shard (built fresh
        from each shard's live rows). The planner (core/query.py)
        accelerates scatter-gather queries only when EVERY shard's
        discovery index is attached and fresh."""
        return [sh.attach_discovery(cfg) for sh in self.shards]

    @_locked
    def rebuild_discovery(self) -> None:
        """Rebuild every attached per-shard discovery index from live
        rows — the post-snapshot-ingest / post-restore hook."""
        for sh in self.shards:
            sh.rebuild_discovery()

    @_locked
    def attach_rollups(self, hierarchy) -> None:
        """Attach ONE hierarchy.HierarchyIndex across all shards: any
        shard's structural rewrite invalidates it, any shard's
        compaction notifies it (rollups are namespace-global — the
        mirror spans shard boundaries by path)."""
        self.rollups = hierarchy
        for sh in self.shards:
            sh.rollups = hierarchy

    def slot_stats(self) -> Dict[str, float]:
        """Deployment-wide arena occupancy (per-shard stats summed; the
        dead fraction is over ALL assigned slots)."""
        per = [sh.slot_stats() for sh in self.shards]
        n = sum(p["slots"] for p in per)
        live = sum(p["live"] for p in per)
        return {"slots": n, "live": live, "dead": n - live,
                "dead_fraction": (n - live) / n if n else 0.0}

    @_locked
    def compact(self, threshold: float = 0.0) -> int:
        """Compact every shard whose dead-slot fraction exceeds
        ``threshold`` (DESIGN.md §9.2) — compaction is naturally
        per-shard, so a deployment reclaims its hottest-churning
        partitions without rewriting the rest. Each shard's slot map is
        rebuilt through this index's ``slot_map_factory``. Returns total
        slots reclaimed."""
        return sum(
            sh.compact(slot_map_factory=self.slot_map_factory)
            for sh in self.shards
            if sh.slot_stats()["dead_fraction"] > threshold)

    # -- checkpoint / restore (DESIGN.md §10.3) -------------------------------

    def state_dict(self) -> Dict:
        """Per-shard arena snapshots plus the routing parameters — the
        shard count MUST ride along: restoring into a different shard
        count would silently re-route every subject."""
        return {
            "kind": "sharded",
            "n_shards": self.n_shards,
            "kernel_route_min": self.kernel_route_min,
            "route_width": self.route_width,
            "shards": [sh.state_dict() for sh in self.shards],
        }

    @_locked
    def load_state(self, state: Dict, slot_map_factory=None) -> None:
        assert state["kind"] == "sharded", state.get("kind")
        if state["n_shards"] != self.n_shards:
            raise ValueError(
                f"checkpoint has {state['n_shards']} shards, this index "
                f"has {self.n_shards}: restore into a matching layout "
                "(resharding goes through snapshot re-ingest)")
        if slot_map_factory is None:
            slot_map_factory = self.slot_map_factory
        self.kernel_route_min = state["kernel_route_min"]
        self.route_width = state["route_width"]
        self._bind_route_rows()
        for sh, sub in zip(self.shards, state["shards"]):
            sh.load_state(sub, slot_map_factory)

    @classmethod
    def from_state(cls, state: Dict,
                   slot_map_factory=None) -> "ShardedPrimaryIndex":
        idx = cls(n_shards=state["n_shards"],
                  kernel_route_min=state["kernel_route_min"],
                  route_width=state["route_width"],
                  slot_map_factory=slot_map_factory)
        idx.load_state(state, slot_map_factory)
        return idx

    def checkpoint(self, path: str, meta: Optional[Dict] = None) -> None:
        """One atomic msgpack+zstd file for the whole deployment (see
        PrimaryIndex.checkpoint)."""
        from repro.core.index import atomic_write_blob
        atomic_write_blob(path, {"state": self.state_dict(), "meta": meta})

    @classmethod
    def restore(cls, path: str,
                slot_map_factory=None) -> "ShardedPrimaryIndex":
        from repro.core.index import read_blob
        return cls.from_state(read_blob(path)["state"], slot_map_factory)

    # -- reads (scatter-gather) -----------------------------------------------

    def live(self) -> Dict[str, np.ndarray]:
        """Gather: per-shard ``live()`` views merged into one
        schema-stable dict (row order is shard-major; queries treat rows
        as a set). Columns only some shards carry are zero-filled
        elsewhere, mirroring the monolith's sparse-column rule.
        Per-shard views are taken copy-free (``live(copy=False)``): the
        concatenate below materializes them, so compacted shards feed
        the merge straight from their arenas."""
        views = [sh.live(copy=False) for sh in self.shards]
        counts = [len(v["path"]) for v in views]
        keys = {}
        for v in views:
            for k, col in v.items():
                keys.setdefault(k, col.dtype)
        out = {}
        for k, dt in keys.items():
            out[k] = np.concatenate(
                [v[k] if k in v else np.zeros(c, dt)
                 for v, c in zip(views, counts)])
        return out

    def live_paths(self) -> np.ndarray:
        return np.concatenate([sh.live_paths(copy=False)
                               for sh in self.shards])

    def get_record(self, path: str, keys: Sequence[str] = (
            "uid", "gid", "size", "mtime")) -> Optional[Dict[str, float]]:
        return self.shards[self.shard_of(path)].get_record(path, keys)

    def lookup(self, path: str) -> Optional[Dict[str, float]]:
        """Point query: one shard, one slot-map probe."""
        return self.shards[self.shard_of(path)].lookup(path)

    def probe(self, path: str, keys: Sequence[str] = (
            "type", "size", "atime", "mtime")):
        """Liveness-aware point read (rollup mirror sync): routed to the
        owning shard; cross-shard repath migration is invisible here
        because the route is recomputed per probe."""
        return self.shards[self.shard_of(path)].probe(path, keys)

    def shard_sizes(self) -> np.ndarray:
        """Live record count per shard (balance diagnostics)."""
        return np.array([len(sh) for sh in self.shards], np.int64)

    def __len__(self) -> int:
        return sum(len(sh) for sh in self.shards)


def index_from_state(state: Dict, slot_map_factory=None):
    """Rebuild whichever index shape a ``state_dict`` came from — the
    durable pipeline's restore path doesn't care which layout it
    checkpointed (DESIGN.md §10.3)."""
    if state["kind"] == "sharded":
        return ShardedPrimaryIndex.from_state(state, slot_map_factory)
    return PrimaryIndex.from_state(state, slot_map_factory)
