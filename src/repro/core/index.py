"""Dual metadata index (paper §III-A; DESIGN.md §3): primary (per-object)
+ aggregate (per-principal summaries), with version-based idempotent
ingest.

The primary index is a columnar store over MetadataTable columns plus the
host path array; the aggregate index holds DDSketch summaries per
principal. Both expose the record schema the paper ingests into Globus
Search (subject / visible_to / content) so the web-interface layer and
every reader see a uniform shape.

Consistency semantics (DESIGN.md §6): every mutation carries a version on
one monotone logical clock shared by snapshot ingest and event ingest (a
snapshot's version is the changelog sequence number at scan time). A
record with a higher version never regresses to a lower one, so replaying
any suffix of the change history is idempotent. Readers on the LIVE
index see it *between* ingest calls only — each batch mutation is
applied column-wise, so a reader interleaving with an ingest thread
could observe a half-applied batch. Concurrent readers therefore go
through MVCC snapshot views instead (DESIGN.md §12): ``snapshot()``
pins a read-only view under the index write lock, mutating paths
copy-on-first-write any arena an open snapshot still references, and
closing the view releases its pin (core/mvcc.py; served by
core/query_service.py). The freshness contract queries rely on is the
watermark exported by event_ingest.EventIngestor.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import os
import threading
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import msgpack
import numpy as np
import zstandard as zstd

from repro.core import metadata as md
from repro.core.sketches import ddsketch as dds
from repro.core.telemetry import resolve as _resolve_tel


def atomic_write_blob(path: str, obj, pre_replace: Optional[Callable] = None
                      ) -> None:
    """msgpack+zstd ``obj`` to ``path`` atomically: the bytes land in a
    sibling tmp file first and ``os.replace`` publishes them in one
    step, so a crash mid-write leaves the previous checkpoint intact —
    readers see the old file or the new one, never a torn hybrid.
    ``pre_replace`` is a fault-injection hook (tests/test_crash_recovery)
    called between the tmp write and the publish."""
    blob = zstd.ZstdCompressor(level=3).compress(
        msgpack.packb(obj, use_bin_type=True))
    tmp = f"{path}.tmp.{os.getpid()}"
    try:
        with open(tmp, "wb") as f:
            f.write(blob)
        if pre_replace is not None:
            pre_replace()
        os.replace(tmp, path)
    except BaseException:
        with contextlib.suppress(OSError):
            os.unlink(tmp)
        raise
    # a REAL crash mid-write runs no handler: sweep tmp strays from
    # DEAD writers now that a good checkpoint exists (a live pid's tmp
    # may be a concurrent writer mid-publish — leave it alone)
    base = os.path.basename(path) + ".tmp."
    d = os.path.dirname(path) or "."
    for stray in os.listdir(d):
        if not stray.startswith(base):
            continue
        try:
            pid = int(stray[len(base):])
            os.kill(pid, 0)              # raises if the pid is gone
        except (ValueError, ProcessLookupError):
            with contextlib.suppress(OSError):
                os.unlink(os.path.join(d, stray))
        except OSError:
            pass                         # alive but not ours (EPERM)


def read_blob(path: str):
    with open(path, "rb") as f:
        blob = zstd.ZstdDecompressor().decompress(f.read())
    # int map keys (the ingestor's fid-keyed state tables) are legal
    return msgpack.unpackb(blob, raw=False, strict_map_key=False)


def pack_array(a: np.ndarray) -> List:
    """One checkpoint wire format for every ndarray: [dtype, shape,
    raw bytes] — shared by the index arenas and the ingestor's sketch /
    counts state (event_ingest.py), so serialization fixes land once."""
    a = np.asarray(a)
    return [str(a.dtype), list(a.shape), a.tobytes()]


def unpack_array(packed: List) -> np.ndarray:
    dtype, shape, data = packed
    return np.frombuffer(data, np.dtype(dtype)).reshape(shape).copy()


def bucket_pow2(n: int, floor: int = 1) -> int:
    """Power-of-two padded size >= n: callers that pad device batches to
    this keep the jit shape universe at O(log batch) instead of one
    compile per batch size."""
    return max(floor, 1 << max(0, int(n - 1).bit_length()))


def pad_1d(a: np.ndarray, n: int, fill=0) -> np.ndarray:
    if len(a) >= n:
        return a
    return np.concatenate([a, np.full(n - len(a), fill, a.dtype)])


def _contig_slice(slots: np.ndarray) -> Optional[slice]:
    """slice(lo, hi) iff ``slots`` is exactly arange(lo, hi) — the common
    bulk-ingest shape (fresh or same-order re-scan), where column writes
    collapse from fancy scatters to memcpy slices."""
    n = len(slots)
    if n == 0:
        return None
    lo = int(slots[0])
    if int(slots[-1]) - lo + 1 != n:
        return None
    if n > 1 and not (np.diff(slots) == 1).all():
        return None
    return slice(lo, lo + n)


@functools.partial(jax.jit, static_argnums=(0,))
def _summary_jit(cfg, state, qs, sel=None):
    if sel is not None:
        state = jax.tree.map(lambda s: s[sel], state)
    return dds.summary(cfg, state, qs)


class DictSlotMap:
    """Subject -> slot assignment backed by a plain Python dict — the
    monolithic index's default. The slot-map protocol (``assign`` /
    ``lookup`` / ``get`` / ``__len__``) is what lets the sharded index
    (core/sharded_index.py) swap in a vectorized hash-keyed map without
    touching the columnar store logic."""

    def __init__(self):
        self._d: Dict[str, int] = {}

    def __len__(self) -> int:
        return len(self._d)

    def get(self, path: str) -> Optional[int]:
        return self._d.get(path)

    def get_or_add(self, path: str) -> Tuple[int, bool]:
        slot = self._d.get(path)
        if slot is not None:
            return slot, False
        slot = len(self._d)
        self._d[path] = slot
        return slot, True

    def assign(self, paths: Sequence[str],
               hashes: Optional[np.ndarray] = None
               ) -> Tuple[np.ndarray, np.ndarray]:
        """(slots, new_mask) for a batch; new paths get fresh slots in
        first-occurrence order (``hashes`` is accepted for protocol
        parity and ignored — the dict keys on the full string)."""
        n = len(paths)
        slots = np.empty(n, np.int64)
        new_mask = np.zeros(n, bool)
        d = self._d
        for i, p in enumerate(paths):   # the only host loop
            s = d.get(p)
            if s is None:
                s = len(d)
                d[p] = s
                new_mask[i] = True
            slots[i] = s
        return slots, new_mask

    def lookup(self, paths: Sequence[str],
               hashes: Optional[np.ndarray] = None) -> np.ndarray:
        """Slots for known paths, -1 for unknown (no insertion)."""
        n = len(paths)
        return np.fromiter((self._d.get(p, -1) for p in paths),
                           np.int64, n)


def _locked(fn):
    """Serialize a mutating index op against ``snapshot()`` pinning:
    both run under the index's reentrant write lock, so a snapshot never
    pins mid-write arenas. The lock is reentrant, so composite writers
    (the event ingestor's apply, which wraps several mutations in
    ``write_lock()``) pay one acquisition; reads stay lock-free."""
    @functools.wraps(fn)
    def wrapper(self, *args, **kwargs):
        with self._lock:
            return fn(self, *args, **kwargs)
    return wrapper


@dataclasses.dataclass
class PrimaryIndex:
    """Columnar per-object index. Ingest is idempotent by (subject,
    version): re-ingesting a snapshot version replaces matching subjects;
    older-version records are invalidated (paper §IV-A1)."""

    columns: Dict[str, np.ndarray] = dataclasses.field(default_factory=dict)
    paths: np.ndarray = dataclasses.field(
        default_factory=lambda: np.empty(0, object))
    version: np.ndarray = dataclasses.field(
        default_factory=lambda: np.zeros(0, np.int64))
    alive: np.ndarray = dataclasses.field(
        default_factory=lambda: np.zeros(0, bool))
    slot_map: DictSlotMap = dataclasses.field(default_factory=DictSlotMap)
    #: compaction folds reclaimed tombstone versions into this floor: a
    #: subject UNKNOWN to the slot map may be a reclaimed tombstone, so
    #: fresh slots materialize carrying version=floor (an implicit
    #: tombstone) and the normal >= gate decides resurrection — a stale
    #: replay or pre-compaction scan cannot resurrect a compacted-away
    #: delete (DESIGN.md §9.2)
    tombstone_floor: int = 0
    #: monotone counter of mutating operations — the discovery index's
    #: freshness clock: an attached discovery.ShardDiscovery is exact
    #: iff it has observed every epoch (DESIGN.md §11.3). NOT
    #: serialized: restore invalidates and rebuilds derived state.
    mutation_epoch: int = 0
    #: optional attached discovery.ShardDiscovery (secondary indexes);
    #: every mutating op below publishes touched slots into it via
    #: ``_mutated`` — structural rewrites invalidate instead
    discovery: Optional[object] = dataclasses.field(
        default=None, repr=False, compare=False)
    #: optional attached hierarchy.HierarchyIndex (subtree rollups,
    #: DESIGN.md §14): structural rewrites the rollup mirror cannot
    #: absorb incrementally invalidate it; compaction (live rows
    #: unchanged) only notifies. NOT serialized.
    rollups: Optional[object] = dataclasses.field(
        default=None, repr=False, compare=False)
    #: MVCC machinery (DESIGN.md §12) — none of it serialized.
    #: Reentrant write lock: every mutator below runs under it
    #: (``_locked``), and ``snapshot()`` pins under it too.
    _lock: threading.RLock = dataclasses.field(
        default_factory=threading.RLock, repr=False, compare=False)
    #: arena names pinned by at least one open snapshot; the next
    #: in-place write to one copies it first (copy-on-first-write)
    _shared: set = dataclasses.field(
        default_factory=set, repr=False, compare=False)
    #: open-snapshot refcounts keyed by the mutation epoch they pinned
    _snap_refs: Dict[int, int] = dataclasses.field(
        default_factory=dict, repr=False, compare=False)
    #: telemetry handle (None: the process default, resolved at
    #: construction); it only observes and is NOT serialized
    telemetry: Optional[object] = dataclasses.field(
        default=None, repr=False, compare=False)

    def __post_init__(self):
        self.telemetry = _resolve_tel(self.telemetry)
        # upsert_batch's stage spans, bound once
        self._span_assign = self.telemetry.span("index.upsert.assign")
        self._span_write = self.telemetry.span("index.upsert.write")

    def _mutated(self, slots: Optional[np.ndarray] = None) -> None:
        """Epoch bump + delta publication to the attached discovery
        index. ``slots=None`` means the mutation cannot be described
        slot-by-slot (bulk snapshot ingest / state load) — the
        discovery state is invalidated and the planner falls back to
        scans until a rebuild. Called at the END of each mutating op,
        so a triggered delta merge reads consistent arenas."""
        self.mutation_epoch += 1
        if slots is None and self.rollups is not None:
            # bulk snapshot ingest / state load: the path-keyed rollup
            # mirror cannot replay that — fall back until reseeded
            self.rollups.invalidate()
        d = self.discovery
        if d is None:
            return
        if slots is None:
            d.invalidate()
        else:
            d.note_slots(slots)
        d.mark_synced(self.mutation_epoch)

    @_locked
    def attach_discovery(self, cfg=None):
        """Create + attach a discovery.ShardDiscovery over this index
        and build it from the current live rows (fresh immediately).
        Returns the discovery index (also at ``self.discovery``)."""
        from repro.core.discovery import ShardDiscovery
        self.discovery = ShardDiscovery(self, cfg)
        self.discovery.rebuild()
        return self.discovery

    @_locked
    def rebuild_discovery(self) -> None:
        """Rebuild the attached discovery index from live rows (no-op
        when none attached) — the post-snapshot / post-restore hook."""
        if self.discovery is not None:
            self.discovery.rebuild()

    @_locked
    def attach_rollups(self, hierarchy) -> None:
        """Attach a hierarchy.HierarchyIndex so structural rewrites
        (``_mutated(None)``) invalidate it and compaction notifies it."""
        self.rollups = hierarchy

    # -- MVCC snapshot views (DESIGN.md §12) ----------------------------------

    def write_lock(self):
        """The reentrant lock serializing mutations against snapshot
        pinning. Composite writers (the event ingestor's apply loop)
        hold it across a whole logical batch so a concurrent
        ``snapshot()`` pins batch boundaries only; the per-mutator
        acquisitions nest inside it for free."""
        return self._lock

    def snapshot(self, freshness: Optional[Dict] = None):
        """Pin a read-only MVCC view of the current state. O(#arenas) —
        the view holds REFERENCES to the live arrays: every arena is
        marked shared here, and the next in-place write to one copies it
        first (``_unshare``), so the view keeps answering from the
        frozen originals while ingest proceeds. ``freshness`` rides
        along uninterpreted (the serving tier pins the ingest watermark
        here, core/query_service.py). Close the view — it is a context
        manager — to release its pin; ``snapshot_stats`` audits pins."""
        from repro.core.mvcc import IndexSnapshot
        with self._lock:
            self._shared = {"paths", "version", "alive", *self.columns}
            view = IndexSnapshot(self, freshness=freshness)
            e = view.mutation_epoch
            self._snap_refs[e] = self._snap_refs.get(e, 0) + 1
            return view

    def _release_snapshot(self, epoch: int) -> None:
        """Refcount decrement for a closing snapshot (close idempotence
        is the view's job). When the last pin at ``epoch`` drops, the
        epoch's entry is reclaimed; when NO pins remain at all, the
        arenas stop being shared and later mutations write in place
        again without a defensive copy."""
        with self._lock:
            left = self._snap_refs.get(epoch, 0) - 1
            if left > 0:
                self._snap_refs[epoch] = left
            else:
                self._snap_refs.pop(epoch, None)
            if not self._snap_refs:
                self._shared.clear()

    def snapshot_stats(self) -> Dict[str, int]:
        """Pin accounting (the leak check's probe): currently-open
        snapshot views and the distinct mutation epochs they pinned."""
        with self._lock:
            return {"open_snapshots": int(sum(self._snap_refs.values())),
                    "pinned_epochs": len(self._snap_refs)}

    def _unshare(self, *names: str) -> None:
        """Copy-on-first-write: any arena pinned by an open snapshot is
        replaced with a private copy before an in-place write, so pinned
        views keep reading the frozen original. Wholesale rebinds
        (capacity growth, ``compact``, ``load_state``) allocate fresh
        arrays for everything and clear the shared set instead."""
        shared = self._shared
        if not shared:
            return
        for k in names:
            if k not in shared:
                continue
            shared.discard(k)
            if k == "paths":
                self.paths = self.paths.copy()
            elif k == "version":
                self.version = self.version.copy()
            elif k == "alive":
                self.alive = self.alive.copy()
            elif k in self.columns:
                self.columns[k] = self.columns[k].copy()

    @property
    def _slot(self):
        """Back-compat alias: the slot map supports ``get`` and ``len``
        like the dict it replaced."""
        return self.slot_map

    def ingest_table(self, table: md.MetadataTable, version: int) -> int:
        """Bulk snapshot ingest (vectorized; idempotent by version). The
        table's ``path_hash`` column (the hashshard kernel's FNV family)
        rides along for slot maps that key on hashes (slot-map protocol;
        the sharded layer also routes on it, DESIGN.md §8)."""
        files = md.files_only(table)
        # raw column views: ingest_columns casts to STANDARD_COLUMNS
        # dtypes on assignment (one fused pass, no astype staging)
        cols = {k: getattr(files, k) for k in self.STANDARD_COLUMNS}
        return self.ingest_columns(files.paths, cols, version)

    @_locked
    def ingest_columns(self, paths: np.ndarray,
                       cols: Dict[str, np.ndarray], version: int,
                       rows: Optional[np.ndarray] = None,
                       hashes: Optional[np.ndarray] = None) -> int:
        """`ingest_table` after preprocessing: column arrays aligned with
        ``paths`` (or indexed by ``rows`` — the sharded split passes the
        FULL table columns plus each shard's row-index array, so the
        gather, the device-dtype cast, and the arena write fuse into one
        C pass per column). Storage dtypes follow STANDARD_COLUMNS for
        known columns (assignment casts on the fly). Paths are written
        for NEW slots only (existing slots hold the identical subject),
        and contiguous slot runs take memcpy slice writes instead of
        fancy scatters."""
        if hashes is None:
            hashes = np.asarray(cols["path_hash"], np.uint32)
            if rows is not None:
                hashes = hashes[rows]

        def dtype_of(k, v):
            return self.STANDARD_COLUMNS.get(k, v.dtype)

        if not self.columns:
            self.columns = {k: np.zeros(0, dtype_of(k, v))
                            for k, v in cols.items()}
        slots, new_mask = self.slot_map.assign(paths, hashes)
        n_new = int(new_mask.sum())
        self._ensure_capacity(max(0, len(self.slot_map) - len(self.paths)))
        for k, v in cols.items():
            if k not in self.columns:
                self.columns[k] = np.zeros(len(self.paths), dtype_of(k, v))
        self._unshare("version", "alive", *cols)
        if n_new:
            self._unshare("paths")
            self.paths[slots[new_mask]] = paths[new_mask]
            if self.tombstone_floor:
                # fresh slots may be reclaimed tombstones: they start at
                # the compaction floor so the >= gate below decides
                self.version[slots[new_mask]] = self.tombstone_floor
        sl = _contig_slice(slots)
        if sl is not None and rows is None:
            mask = version >= self.version[sl]
            if mask.all():
                for k, v in cols.items():
                    self.columns[k][sl] = v
                sel = sl
            else:
                sel = slots[mask]
                for k, v in cols.items():
                    self.columns[k][sel] = v[mask]
        elif sl is not None:
            mask = version >= self.version[sl]
            if mask.all():
                for k, v in cols.items():
                    self.columns[k][sl] = v[rows]    # fused gather+cast
                sel = sl
            else:
                sel = slots[mask]
                rsel = rows[mask]
                for k, v in cols.items():
                    self.columns[k][sel] = v[rsel]
        else:
            mask = version >= self.version[slots]
            sel = slots[mask]
            rsel = mask if rows is None else rows[mask]
            for k, v in cols.items():
                self.columns[k][sel] = v[rsel]
        self.version[sel] = version
        self.alive[sel] = True
        self.invalidate_older(version)
        return n_new

    def _ensure_capacity(self, extra: int):
        cur = len(self.paths)
        need = cur + extra
        cap = max(1024, cur)
        while cap < need:
            cap *= 2
        if cap == cur:
            return
        # growth/compaction are cold paths: families looked up per call
        tel = self.telemetry
        tel.counter("index_arena_growth_total",
                    "arena doubling events").inc()
        tel.counter("index_arena_grown_rows_total",
                    "rows of fresh arena capacity allocated").inc(cap - cur)
        self.paths = np.concatenate(
            [self.paths, np.empty(cap - cur, object)])
        self.version = np.concatenate(
            [self.version, np.zeros(cap - cur, np.int64)])
        self.alive = np.concatenate([self.alive, np.zeros(cap - cur, bool)])
        for k, v in self.columns.items():
            self.columns[k] = np.concatenate(
                [v, np.zeros(cap - cur, v.dtype)])
        # growth rebound every arena to a fresh array: open snapshots
        # keep their pinned originals, nothing is shared any more
        self._shared.clear()

    @_locked
    def _put(self, path: str, fields: Dict, version: int) -> int:
        if not self.columns:
            self.columns = {k: np.zeros(0, np.asarray(v).dtype)
                            for k, v in fields.items()}
        slot, is_new = self.slot_map.get_or_add(path)
        self._unshare("paths", "version", "alive", *fields)
        new = 0
        if is_new:
            self._ensure_capacity(max(0, len(self.slot_map)
                                      - len(self.paths)))
            self.paths[slot] = path
            if self.tombstone_floor:
                self.version[slot] = self.tombstone_floor
            new = 1
        if version >= self.version[slot]:
            for k, v in fields.items():
                self.columns[k][slot] = v
            self.version[slot] = version
            self.alive[slot] = True
        self._mutated(np.array([slot], np.int64))
        return new

    def upsert(self, path: str, fields: Dict, version: int) -> None:
        """Single-record upsert (paper §IV-B3). Applied only when
        ``version >= `` the record's stored version; otherwise a no-op
        (stale event). Prefer ``upsert_batch`` on the hot path."""
        self._put(path, fields, version)

    @_locked
    def delete(self, path: str, version: int) -> None:
        """Single-record tombstone: the slot stays allocated (columns keep
        their last values) but the record leaves every live() view. A
        later upsert with ``version >=`` the tombstone's resurrects the
        slot."""
        slot = self._slot.get(path)
        if slot is not None and version >= self.version[slot]:
            self._unshare("alive", "version")
            self.alive[slot] = False
            self.version[slot] = version
            self._mutated(np.array([slot], np.int64))

    # -- batched event-path mutations (paper §IV-B3; DESIGN.md §6) ------------

    @_locked
    def upsert_batch(self, paths: Sequence[str], fields: Dict[str, np.ndarray],
                     versions: np.ndarray,
                     hashes: Optional[np.ndarray] = None) -> np.ndarray:
        """Vectorized columnar upsert for a coalesced event batch.

        ``fields`` maps column name -> (N,) array; only the given columns
        are written (missing columns of new records stay zero until a
        snapshot or a richer event fills them — the paper's event records
        are sparser than its snapshot rows). ``versions`` is (N,) int64 on
        the shared logical clock (changelog seq of each surviving
        representative). Rows whose version is older than the stored
        record are dropped (idempotent replay). Duplicate paths within a
        batch must be ordered by seq ascending — numpy scatter gives
        last-occurrence-wins, matching changelog order.

        Slot assignment is one slot-map sweep (the only host loop in the
        dict-backed default); every column write is a fancy-index
        scatter. ``hashes`` optionally forwards precomputed FNV path
        hashes (``fields["path_hash"]`` on the event path) to hash-keyed
        slot maps. Returns a (N,) bool mask marking one row per subject
        that ENTERED the live set — a brand-new slot or a tombstoned slot
        resurrected by this batch — i.e. the counting pipeline's +1
        delta (a recreate after a delete must count again).
        """
        n = len(paths)
        if n == 0:
            return np.zeros(0, bool)
        versions = np.broadcast_to(np.asarray(versions, np.int64), (n,))
        if not self.columns:
            self.columns = {k: np.zeros(0, np.asarray(v).dtype)
                            for k, v in fields.items()}
        for k, v in fields.items():
            if k not in self.columns:
                self.columns[k] = np.zeros(len(self.paths),
                                           np.asarray(v).dtype)
        if hashes is None and "path_hash" in fields:
            hashes = np.asarray(fields["path_hash"], np.uint32)
        with self._span_assign:
            slots, new_mask = self.slot_map.assign(paths, hashes)
        with self._span_write:
            self._ensure_capacity(max(0, len(self.slot_map)
                                      - len(self.paths)))
            self._unshare("paths", "version", "alive", *fields)
            if new_mask.any():
                self.paths[slots[new_mask]] = np.asarray(
                    paths, object)[new_mask]
                if self.tombstone_floor:
                    # fresh slots may be reclaimed tombstones: start them
                    # at the compaction floor so the >= gate below decides
                    self.version[slots[new_mask]] = self.tombstone_floor
            prev_alive = self.alive[slots] & ~new_mask   # pre-batch liveness
            ok = versions >= self.version[slots]
            sel = slots[ok]
            for k, v in fields.items():
                self.columns[k][sel] = np.asarray(v)[ok]
            self.version[sel] = versions[ok]
            self.alive[sel] = True
            entered = ok & ~prev_alive
            # one +1 per slot even if the subject repeats within the batch
            idx = np.nonzero(entered)[0]
            out = np.zeros(n, bool)
            if len(idx):
                _, first_pos = np.unique(slots[idx], return_index=True)
                out[idx[first_pos]] = True
            # discovery delta: every touched slot (gated rows included —
            # over-noting only costs a re-verify, never a wrong answer)
            self._mutated(slots)
        return out

    @_locked
    def delete_batch(self, paths: Sequence[str],
                     versions: np.ndarray,
                     hashes: Optional[np.ndarray] = None) -> np.ndarray:
        """Vectorized tombstones. Unknown subjects are ignored (a delete
        for a record the index never saw — e.g. created and removed
        between snapshots with OPEN filtering on). Returns a (N,) bool
        mask of rows that transitioned live -> dead (the counting
        pipeline's -1 delta)."""
        n = len(paths)
        if n == 0 or not len(self.slot_map):      # nothing indexed yet
            return np.zeros(n, bool)
        versions = np.broadcast_to(np.asarray(versions, np.int64), (n,))
        slots = self.slot_map.lookup(paths, hashes)
        known = slots >= 0
        s = np.clip(slots, 0, None)
        ok = known & (versions >= self.version[s])
        was_alive = self.alive[s] & ok
        sel = s[ok]
        self._unshare("alive", "version")
        self.alive[sel] = False
        self.version[sel] = versions[ok]
        if known.any():
            self._mutated(s[known])
        return was_alive

    @_locked
    def invalidate_older(self, version: int) -> int:
        """Records from snapshots older than `version` are dead — this is
        how periodic re-ingest detects deletions. The tombstones carry
        `version` (the snapshot asserted absence at that point of the
        logical clock), so replaying a pre-snapshot event suffix cannot
        resurrect them."""
        n = len(self.slot_map)
        stale = self.alive[:n] & (self.version[:n] < version)
        self._unshare("alive", "version")
        self.alive[:n] &= ~stale
        self.version[:n][stale] = version
        # a snapshot speaks for the WHOLE namespace (and ingest_columns
        # lands here after its bulk writes): the attached discovery
        # index cannot absorb that slot-by-slot — invalidate; drivers
        # rebuild_discovery() after the load (DESIGN.md §11.3)
        self._mutated(None)
        return int(stale.sum())

    # -- tombstone compaction (DESIGN.md §9.2) --------------------------------

    def slot_stats(self) -> Dict[str, float]:
        """Arena occupancy: assigned slots, live records, and the
        dead-slot fraction the compaction threshold is compared against
        (core/reconcile.py)."""
        n = len(self.slot_map)
        live = int(self.alive[:n].sum())
        return {"slots": n, "live": live, "dead": n - live,
                "dead_fraction": (n - live) / n if n else 0.0}

    @_locked
    def compact(self, slot_map_factory=None) -> int:
        """Rewrite the arenas to live-only rows and rebuild the slot map
        (DESIGN.md §9.2). Tombstoned slots are never reclaimed by normal
        ingest, so every ``live()`` scan pays for all-time deletes;
        compaction reclaims them. Surviving records keep their versions
        (the idempotent-replay clock is untouched), and a live run that
        is already contiguous takes memcpy slice copies instead of fancy
        gathers. The slot map is rebuilt through the pluggable protocol:
        ``assign`` numbers fresh subjects in first-occurrence order, so
        the new map (``slot_map_factory()``, defaulting to the current
        map's type) is identity-aligned with the compacted arenas.
        Returns the number of slots reclaimed.

        Reclaimed tombstone versions fold into ``tombstone_floor``
        (their max), so dropping the slots cannot break the version
        gate: a later write for a subject the slot map no longer knows
        materializes its fresh slot AT the floor, and only versions
        ``>=`` the floor resurrect — a stale event replay or a
        pre-compaction scan is blocked exactly as the individual
        tombstones would have blocked it."""
        n = len(self.slot_map)
        live_slots = np.nonzero(self.alive[:n])[0]
        dead = n - len(live_slots)
        if dead == 0:
            return 0
        tel = self.telemetry
        t0 = tel.clock()
        dead_vers = self.version[:n][~self.alive[:n]]
        self.tombstone_floor = max(self.tombstone_floor,
                                   int(dead_vers.max()))
        sl = _contig_slice(live_slots)

        def take(a):
            return a[sl].copy() if sl is not None else a[live_slots]

        self.paths = take(self.paths[:n])
        self.version = take(self.version[:n])
        self.columns = {k: take(v[:n]) for k, v in self.columns.items()}
        self.alive = np.ones(len(self.paths), bool)
        if slot_map_factory is None:
            slot_map_factory = type(self.slot_map)
        new_map = slot_map_factory()
        _, new_mask = new_map.assign(self.paths,
                                     self.columns.get("path_hash"))
        assert new_mask.all() and len(new_map) == len(self.paths)
        self.slot_map = new_map
        # every arena was rebound to a fresh array above; open snapshots
        # keep their pinned pre-compaction arrays (and their pinned slot
        # map object — compaction builds a NEW map, never mutates the old)
        self._shared.clear()
        # slot ids just changed under every discovery run: invalidate
        # and rebuild from the (now live-only) rows so the planner keeps
        # accelerating across compactions (DESIGN.md §11.3)
        self.mutation_epoch += 1
        if self.discovery is not None:
            self.discovery.rebuild()
        if self.rollups is not None:
            # live records are unchanged — the path-keyed rollup mirror
            # survives compaction by construction; notify for stats
            self.rollups.note_compaction()
        tel.histogram("index_compact_seconds",
                      "one arena compaction").observe(tel.clock() - t0)
        tel.counter("index_compact_reclaimed_slots_total",
                    "tombstoned slots reclaimed by compaction").inc(dead)
        return dead

    # -- checkpoint / restore (DESIGN.md §10.3) -------------------------------

    def state_dict(self) -> Dict:
        """Serializable arena snapshot: paths, columns, versions,
        liveness, and the tombstone floor — everything a restore needs
        to be byte-identical to this index. Slots are NOT serialized:
        the slot map numbers subjects in first-occurrence order, so
        ``paths`` (which is arena order) rebuilds it exactly."""
        n = len(self.slot_map)
        return {
            "kind": "primary",
            "paths": [str(p) for p in self.paths[:n]],
            "version": pack_array(self.version[:n]),
            "alive": pack_array(self.alive[:n]),
            "columns": {k: pack_array(v[:n])
                        for k, v in self.columns.items()},
            "tombstone_floor": int(self.tombstone_floor),
        }

    @_locked
    def load_state(self, state: Dict, slot_map_factory=None) -> None:
        """Rebuild this index in place from ``state_dict`` output. The
        slot map is reassigned from the stored path order (identity
        alignment with the arenas, like ``compact``)."""
        assert state["kind"] == "primary", state.get("kind")
        paths = np.asarray(state["paths"], object)
        if slot_map_factory is None:
            slot_map_factory = type(self.slot_map)
        new_map = slot_map_factory()
        self.columns = {k: unpack_array(v)
                        for k, v in state["columns"].items()}
        if len(paths):
            slots, new_mask = new_map.assign(
                paths, self.columns.get("path_hash"))
            assert new_mask.all() and np.array_equal(
                slots, np.arange(len(paths))), "corrupt checkpoint paths"
        self.slot_map = new_map
        self.paths = paths
        self.version = unpack_array(state["version"])
        self.alive = unpack_array(state["alive"])
        self.tombstone_floor = int(state["tombstone_floor"])
        # all arenas rebound wholesale: nothing is shared with open
        # snapshots any more (they keep the pre-restore arrays)
        self._shared.clear()
        # discovery state is derived, not serialized: invalidate here;
        # the restore path rebuilds deterministically (DESIGN.md §11.4)
        self._mutated(None)

    @classmethod
    def from_state(cls, state: Dict, slot_map_factory=None) -> "PrimaryIndex":
        idx = cls() if slot_map_factory is None else \
            cls(slot_map=slot_map_factory())
        idx.load_state(state, slot_map_factory)
        return idx

    def checkpoint(self, path: str, meta: Optional[Dict] = None) -> None:
        """Persist the index (msgpack+zstd, atomic tmp+rename — a crash
        mid-checkpoint leaves the previous file intact). ``meta`` rides
        along uninterpreted: the durable pipeline stores its consumed-
        offset barrier here (core/stream_pipeline.py)."""
        atomic_write_blob(path, {"state": self.state_dict(), "meta": meta})

    @classmethod
    def restore(cls, path: str, slot_map_factory=None) -> "PrimaryIndex":
        """Load a ``checkpoint`` file into a fresh index, byte-identical
        to the one that wrote it (live view, versions, floor)."""
        return cls.from_state(read_blob(path)["state"], slot_map_factory)

    # -- views ----------------------------------------------------------------

    #: the Table-II columns every reader may assume exist; missing ones
    #: (sparse event records, empty index) materialize as zeros
    STANDARD_COLUMNS = {
        "path_hash": np.uint32, "parent": np.int32, "depth": np.int32,
        "type": np.int32, "mode": np.int32, "uid": np.int32,
        "gid": np.int32, "size": np.float32, "atime": np.float32,
        "ctime": np.float32, "mtime": np.float32, "fileset": np.int32,
    }

    def live(self, copy: bool = True) -> Dict[str, np.ndarray]:
        """Snapshot view of all live records, schema-stable: queries can
        rely on every STANDARD_COLUMNS key being present (zeros when no
        ingest has populated it — e.g. events carry no mode bits).

        ``copy=False`` may return arena slice VIEWS on the all-alive
        fast path — for consumers that immediately materialize anyway
        (the sharded scatter-gather merge concatenates per shard, so an
        intermediate defensive copy would be pure waste). Treat the
        result as read-only and consume it before the next mutation."""
        n = len(self.slot_map)
        mask = self.alive[:n]
        if mask.all():
            # compacted / never-deleted arenas: contiguous slice copies
            # (memcpy) instead of a boolean gather per column — the
            # scan-query payoff compaction buys (DESIGN.md §9.2)
            out = {k: v[:n].copy() if copy else v[:n]
                   for k, v in self.columns.items()}
            out["path"] = self.paths[:n].copy() if copy else self.paths[:n]
            m = n
        else:
            out = {k: v[:n][mask] for k, v in self.columns.items()}
            out["path"] = self.paths[:n][mask]
            m = int(mask.sum())
        for k, dt in self.STANDARD_COLUMNS.items():
            if k not in out:
                out[k] = np.zeros(m, dt)
        return out

    def live_paths(self, copy: bool = True) -> np.ndarray:
        """Paths of live records only — no column copies. Path-predicate
        queries (QueryEngine.find_by_name) read this instead of the full
        ``live()`` materialization. ``copy=False`` mirrors ``live()``:
        an arena slice view on the all-alive fast path, for consumers
        that materialize immediately (the sharded merge)."""
        n = len(self.slot_map)
        mask = self.alive[:n]
        if mask.all():
            return self.paths[:n].copy() if copy else self.paths[:n]
        return self.paths[:n][mask]

    def get_record(self, path: str, keys: Sequence[str] = (
            "uid", "gid", "size", "mtime")) -> Optional[Dict[str, float]]:
        """Stored fields of the record at ``path`` (live or tombstoned);
        None if the subject was never indexed. The event ingestor's
        fallback fact source for register_tree-only fids."""
        slot = self.slot_map.get(path)
        if slot is None:
            return None
        return {k: self.columns[k][slot].item()
                for k in keys if k in self.columns}

    def probe(self, path: str, keys: Sequence[str] = (
            "type", "size", "atime", "mtime")) -> Optional[
                Tuple[bool, Dict[str, float]]]:
        """Liveness-aware point read for the rollup mirror sync:
        ``None`` if the subject was never indexed, else
        ``(alive, fields)``. Unlike ``lookup`` it reports tombstoned
        subjects too (the mirror must REMOVE those), and unlike
        ``get_record`` it carries liveness."""
        slot = self.slot_map.get(path)
        if slot is None:
            return None
        fields = {k: self.columns[k][slot].item()
                  for k in keys if k in self.columns}
        return bool(self.alive[slot]), fields

    def lookup(self, path: str) -> Optional[Dict[str, float]]:
        """Point query: the full record at ``path`` if it is live, else
        None. One slot-map probe + one row gather — no scan."""
        slot = self.slot_map.get(path)
        if slot is None or not self.alive[slot]:
            return None
        out = {k: v[slot].item() for k, v in self.columns.items()}
        out["path"] = path
        out["version"] = int(self.version[slot])
        return out

    def __len__(self) -> int:
        return int(self.alive[:len(self.slot_map)].sum())


@dataclasses.dataclass
class AggregateIndex:
    """Per-principal summaries (Table III; DESIGN.md §3). Stored as plain
    dict records — under 1 GB even for billion-object systems (paper
    Table VI).

    Consistency: records are published whole per principal — a reader
    never sees a half-written summary, but different principals may
    reflect different watermarks while an event batch is being folded in
    (the paper's per-key eventual consistency)."""

    records: Dict[str, Dict] = dataclasses.field(default_factory=dict)

    def put(self, principal: str, summary: Dict) -> None:
        self.records[principal] = summary

    def get(self, principal: str) -> Optional[Dict]:
        return self.records.get(principal)

    def from_sketch_state(self, cfg, state: Dict, names: Sequence[str],
                          attrs=("size", "atime", "ctime", "mtime"),
                          qs=(0.10, 0.25, 0.50, 0.75, 0.90, 0.99),
                          only: Optional[Sequence[int]] = None,
                          counts: Optional[np.ndarray] = None) -> None:
        """(Re)publish summaries from a (P, A, NB) device sketch state.

        ``only`` restricts publication to the given principal indices —
        the event-ingestion hot path refreshes just the principals an
        event batch touched instead of all P of them (paper §IV-B3).

        ``counts`` optionally supplies EXACT live-object counts per
        principal (shape (P,) — the event ingestor's delta-maintained
        matrix summed over crc32 shards). When given it overrides the
        sketch's additive-only count in published ``file_count`` fields,
        and principals whose count is zero are REMOVED from ``records``
        rather than left to linger: deleting a principal's last record
        must not leave a ghost summary for ``directories_over`` /
        ``per_user_usage`` to report. A FULL republication
        (``only=None``) also removes zero-count principals — the state
        speaks for every principal there. A PARTIAL refresh without
        exact counts does NOT remove: its sketch state may be blind to
        records another ingest path loaded (e.g. an event ingestor's
        state vs snapshot-loaded records), so a zero there only means
        "nothing observed here", and the existing record is left as the
        documented bounded-staleness survivor (DESIGN.md §6.2).
        """
        if only is not None:
            sel = np.asarray(list(only), np.int64)
            if len(sel) == 0:
                return
            # pad the slice to a power-of-two bucket: the jitted
            # gather+summary then sees O(log P) distinct shapes instead
            # of one compile per touched-principal count
            padded = pad_1d(sel, bucket_pow2(len(sel)))
            idx = sel
        else:
            padded = None
            idx = np.arange(len(names))
        summ = {k: np.asarray(v)
                for k, v in _summary_jit(
                    cfg, state, jnp.asarray(qs),
                    None if padded is None else jnp.asarray(padded)
                ).items()}
        quants = summ["quantiles"]                   # (P', A, Q)
        authoritative = counts is not None or only is None
        for row, p in enumerate(idx):
            name = names[int(p)]
            cnt = (float(counts[int(p)]) if counts is not None
                   else float(summ["count"][row, 0]))
            if cnt <= 0:
                if authoritative:
                    self.records.pop(name, None)   # no live records: no ghost
                continue
            if float(summ["count"][row, 0]) <= 0:
                # exact count says live records exist, but THIS sketch
                # never observed them (attrs of snapshot-loaded records
                # live in the snapshot pipeline's state, not the event
                # ingestor's): refresh the count on the existing record
                # rather than publish inf/nan stats from an empty row
                got = self.records.get(name)
                if got is not None:
                    got["file_count"] = cnt
                continue
            content = {"file_count": cnt}
            for ai, attr in enumerate(attrs):
                content[attr] = {
                    "min": float(summ["min"][row, ai]),
                    "max": float(summ["max"][row, ai]),
                    "mean": float(summ["mean"][row, ai]),
                    **{f"p{int(q * 100):02d}": float(quants[row, ai, qi])
                       for qi, q in enumerate(qs)},
                }
                if attr == "size":
                    content[attr]["total"] = float(summ["total"][row, ai])
            self.put(name, content)

    def top_k(self, k: int, key=lambda c: c["size"]["total"]) -> List[Tuple[str, Dict]]:
        items = [(n, c) for n, c in self.records.items()]
        items.sort(key=lambda nc: -key(nc[1]))
        return items[:k]

    def __len__(self) -> int:
        return len(self.records)
