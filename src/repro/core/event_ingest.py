"""Event-based ingestion into the dual index (paper §IV-B; DESIGN.md §6).

The missing half of the ingestion story: snapshot.py bulk-loads a scan,
this module keeps both indexes synchronized from a *changelog event
stream* (Lustre MDT changelog / GPFS watch analogue, events.py), so the
indexed view tracks the file system in real time instead of decaying
until the next scan.

Pipeline per applied batch, mirroring the paper's Flink ingest job:

1. **Coalesce** (paper §IV-B2 rule 1+2, host/numpy): sort by (fid, seq),
   keep the last event per fid as its representative, annihilate
   created-then-deleted fids. All segment facts (last parent, last stat,
   last name) are computed with vectorized last-write-wins scatters — no
   per-event Python loop.
2. **State manager** (paper §IV-B3): fold surviving facts into host
   fid->(parent, name, stat) tables; directory renames re-path every
   live descendant (tombstone at the old subject, upsert at the new one)
   — the paper's rename override.
3. **Primary index**: one vectorized ``upsert_batch`` + ``delete_batch``
   per applied batch (batched slot assignment; columnar scatters).
4. **Aggregate index**: grouped per-principal updates on device — object
   counts through the ``segstats`` kernel, attribute sketches through the
   grouped-DDSketch kernel (compiled on a TPU; their jnp references on
   the CPU, ``repro.kernels.on_tpu``) — then republish only the touched
   principals.

Consistency modes (paper's tunable consistency/latency/freshness knobs):

- ``eager``: every ``ingest()`` call applies immediately. Maximum
  freshness, one device dispatch per call.
- ``buffered``: events accumulate and apply when ``max_buffer_events``
  or the ``freshness_window`` wall-clock deadline is hit (size/time
  batching exactly like the paper's 10 MB / 5 s ingest batcher).
  Maximum throughput; queries may trail the stream by up to the window.

Snapshot -> event handoff: events address objects by fid, the snapshot
index by path. Bootstrap the ingestor with ``register_tree`` (the
scanner's fid -> (parent, name) map) so changelog events on pre-scan
files resolve to the subjects the snapshot loaded; events on unknown
fids fall back to ``#fid`` subjects and are counted in
``metrics["unresolved"]``.

Either way every reader can ask for the **watermark**: the highest
changelog seq folded into the indexes, the number of buffered-but-unapplied
events, and the staleness clock. QueryEngine surfaces it next to query
results (DESIGN.md §6.3).

Discovery-index maintenance (DESIGN.md §11): every apply's primary
mutations — version-gated upserts, tombstones, rename repaths, repair
batches — publish their touched slots into any attached
``discovery.ShardDiscovery`` delta buffers through the primary's
mutation hooks, so replay/repair/rename flows keep the secondary
indexes exact without this module special-casing them; ``freshness()``
exports the resulting ``index_lag`` mark.

What a reader observes mid-ingest: the primary index is updated between
``ingest()`` calls only; within one applied batch, upserts land before
tombstones, and aggregate summaries republish after the primary columns —
so a reader interleaved with an apply can see a subject whose aggregate
summary is one batch older (per-key eventual consistency). Sketch
observations are recorded once per newly-seen subject; attribute updates
and deletes reach the aggregate quantiles at the next snapshot rebuild
(bounded-staleness trade-off, DESIGN.md §6.2).
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import events as ev
from repro.core import metadata as md
from repro.core import snapshot as snap
from repro.core.discovery import index_lag as discovery_index_lag
from repro.core.hierarchy import HierarchyIndex, resolve_paths_host
from repro.core.index import (AggregateIndex, PrimaryIndex, bucket_pow2,
                              pack_array, pad_1d, unpack_array)
from repro.core.sketches import ddsketch as dds
from repro.core.telemetry import resolve as _resolve_tel
from repro.kernels.ddsketch import ops as dd_ops
from repro.kernels.segstats import ops as seg_ops

MODES = ("eager", "buffered")


@dataclasses.dataclass(frozen=True)
class IngestConfig:
    """Knobs for the consistency/latency/freshness trade (paper §V-C)."""

    mode: str = "eager"              # "eager" | "buffered"
    freshness_window: float = 5.0    # buffered: max seconds before an apply
    max_buffer_events: int = 8192    # buffered: size trigger
    pad_to: int = 1024               # pad device batches (stable jit shapes)
    filter_opens: bool = True        # drop OPEN events before coalescing
    update_aggregates: bool = True   # maintain the aggregate index too
    track_hierarchy: bool = True     # maintain subtree rollups (§14)

    def __post_init__(self):
        assert self.mode in MODES, self.mode


@dataclasses.dataclass
class Watermark:
    """Freshness metadata readers attach to query results (DESIGN.md §6.3).

    ``applied_seq`` is the highest changelog sequence number whose effect
    is visible in both indexes; everything at or below it is readable.
    ``pending`` counts buffered events not yet applied (always 0 in eager
    mode). ``last_apply_time`` is on the ingestor's clock (monotonic by
    default) so staleness = clock() - last_apply_time.

    ``reconciled_at`` is when the last anti-entropy reconcile completed
    (core/reconcile.py; 0.0 = never): the moment the index was last
    known to agree with a full snapshot, i.e. the bound on how long
    dropped-event drift can have been accumulating. Like
    ``last_apply_time`` it is ON THE INGESTOR'S CLOCK (monotonic by
    default, NOT wall-clock epoch) — compute ages as clock() minus the
    mark, never compare it against ``time.time``; pass
    ``clock=time.time`` at construction if epoch marks are wanted.
    """

    applied_seq: int = 0
    pending: int = 0
    last_apply_time: float = 0.0
    applied_batches: int = 0
    reconciled_at: float = 0.0


# ---------------------------------------------------------------------------
# device steps (jitted once per (config, padded-shape))
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnums=(0,))
def _sketch_apply(scfg: dds.DDSketchConfig, state, vals, pids, mask):
    """state (P, A, NB); vals (A, N); pids/mask (N,): per-attribute
    grouped update through the DDSketch kernel's entry point."""
    n_principals = state["count"].shape[0]
    for ai in range(vals.shape[0]):
        sub = jax.tree.map(lambda s: s[:, ai], state)
        sub = dd_ops.update_grouped(scfg, sub, vals[ai], pids, n_principals,
                                    mask=mask)
        state = jax.tree.map(lambda s, ns: s.at[:, ai].set(ns), state, sub)
    return state


# shared with AggregateIndex publication: one bucketing rule, one shape
# universe (index.bucket_pow2 / index.pad_1d)
_bucket = bucket_pow2
_pad = pad_1d


class EventIngestor:
    """Consumes changelog event batches, keeps PrimaryIndex + AggregateIndex
    synchronized, and exports a freshness watermark (paper §IV-B).

    Versioning: primary-index versions ARE changelog sequence numbers —
    snapshots and events share one logical clock (give ``ingest_table`` the
    changelog seq at scan time as its version), which is what makes replay
    of any event suffix idempotent (paper §IV-A1).
    """

    def __init__(self, cfg: IngestConfig, pcfg: snap.PipelineConfig,
                 primary: PrimaryIndex, aggregate: AggregateIndex,
                 names: Optional[Dict[int, str]] = None,
                 principal_names: Optional[Sequence[str]] = None,
                 clock: Callable[[], float] = time.monotonic,
                 telemetry=None):
        """``primary`` may be a monolithic ``PrimaryIndex`` or a
        ``sharded_index.ShardedPrimaryIndex`` — the ingestor only uses
        the shared mutation protocol (upsert_batch / delete_batch /
        get_record). With a sharded primary, each coalesced micro-batch
        routes per shard by path hash inside the index; THIS ingestor
        still owns the single global watermark/version clock, so
        freshness semantics are identical. A rename that migrates a
        record between shards is already a delete+upsert pair here (old
        subject tombstone + new subject upsert) and each half routes
        independently (DESIGN.md §8)."""
        self.cfg = cfg
        self.pcfg = pcfg
        self.primary = primary
        self.aggregate = aggregate
        self.clock = clock
        self.watermark = Watermark(last_apply_time=clock())
        #: optional () -> int: events durably produced but not yet
        #: committed behind this ingestor (the durable pipeline's
        #: consumer lag, core/stream_pipeline.py) — surfaced in
        #: freshness() as ``log_lag`` next to the watermark
        self.lag_source: Optional[Callable[[], int]] = None
        #: watermark-advance listeners, called as cb(applied_seq,
        #: mutated) at the END of each apply, still under the primary's
        #: write lock. ``mutated`` is False for no-op applies (e.g. an
        #: all-OPEN batch coalescing to nothing): the watermark moved
        #: but the readable state did not — the serving tier's result
        #: cache keys off exactly this distinction (query_service.py)
        self.on_apply: List[Callable[[int, bool], None]] = []
        self.metrics = {"events_in": 0, "applied": 0, "upserts": 0,
                        "tombstones": 0, "cancelled": 0, "repathed": 0,
                        "applies": 0, "sketch_rows": 0, "unresolved": 0,
                        "reconciles": 0, "repair_upserts": 0,
                        "repair_tombstones": 0}
        # registry instruments next to (never replacing) self.metrics:
        # the dict is serialized by state_dict() and byte-compared by the
        # crash/differential suites, so it stays the durable source of
        # truth while telemetry is the scrape surface
        self.telemetry = _resolve_tel(telemetry)
        self._c_events_in = self.telemetry.counter(
            "ingest_events_total", "changelog events handed to ingestors")
        self._h_apply_s = self.telemetry.histogram(
            "ingest_apply_seconds",
            "one coalesced apply under the write lock")
        self._g_applied_seq = self.telemetry.gauge(
            "ingest_watermark_applied_seq",
            "highest changelog seq visible to readers")
        self._g_pending = self.telemetry.gauge(
            "ingest_pending_events", "buffered events not yet visible")
        # host state-manager tables (fid-keyed)
        self._name: Dict[int, str] = dict(names or {})
        self._parent: Dict[int, int] = {}
        self._children: Dict[int, set] = {}
        self._stat: Dict[int, Dict] = {}
        self._is_dir: Dict[int, bool] = {}
        # device aggregate operator state
        self._sketch_state = dds.init(
            pcfg.sketch, (pcfg.n_principals, len(snap.ATTRS)))
        self.counts = np.zeros((pcfg.n_principals, pcfg.n_shards), np.float32)
        # counts start exact (empty index as far as this ingestor knows)
        # and stay exact under event deltas; a snapshot handoff
        # (register_tree) loads records behind the delta stream's back,
        # so exactness then requires seed_counts() with the snapshot
        # counting pipeline's matrix
        self._counts_seeded = False
        self._tree_registered = False
        self._principal_names = (list(principal_names) if principal_names
                                 else [f"user:{i}" for i in range(pcfg.n_users)]
                                 + [f"group:{i}" for i in range(pcfg.n_groups)]
                                 + [f"dir:{i}" for i in range(pcfg.n_dirs)])
        # subtree-rollup tree (DESIGN.md §14): mirrors the primary's
        # live non-dir subjects by post-mutation probe read-back; owned
        # by this ingestor so every apply/repair/restore keeps it in
        # lockstep with the watermark
        self.hierarchy: Optional[HierarchyIndex] = None
        if cfg.track_hierarchy and hasattr(primary, "probe"):
            self.hierarchy = HierarchyIndex()
            attach = getattr(primary, "attach_rollups", None)
            if attach is not None:
                attach(self.hierarchy)
        # buffered mode
        self._buffer: List[Dict[str, np.ndarray]] = []
        self._buffered = 0
        self._first_buffer_ts: Optional[float] = None

    # -- public surface -------------------------------------------------------

    def ingest(self, batch: Dict[str, np.ndarray],
               names: Optional[Dict[int, str]] = None) -> Dict[str, int]:
        """Feed one changelog micro-batch (events.empty_batch layout).

        ``eager``: applied before this call returns — a subsequent query
        reads every effect. ``buffered``: visible only after the size or
        freshness trigger fires (or an explicit flush()). ``names`` merges
        fid -> path-component bindings (EventStream.names side table).
        """
        if names:
            self._name.update(names)
        n = len(batch["fid"])
        self.metrics["events_in"] += n
        self._c_events_in.inc(n)
        if n == 0:
            return {"applied": 0, "pending": self.watermark.pending}
        if self.cfg.mode == "eager":
            applied = self._apply([batch])
        else:
            self._buffer.append({k: np.asarray(v).copy()
                                 for k, v in batch.items()})
            self._buffered += n
            if self._first_buffer_ts is None:
                self._first_buffer_ts = self.clock()
            self.watermark.pending = self._buffered
            applied = 0
            if (self._buffered >= self.cfg.max_buffer_events
                    or self.clock() - self._first_buffer_ts
                    >= self.cfg.freshness_window):
                applied = self.flush()
        return {"applied": applied, "pending": self.watermark.pending}

    def tick(self) -> int:
        """Time-based flush check for buffered mode (call from the driver
        loop, like IngestBatcher.tick)."""
        if (self._buffer and self._first_buffer_ts is not None
                and self.clock() - self._first_buffer_ts
                >= self.cfg.freshness_window):
            return self.flush()
        return 0

    def flush(self) -> int:
        """Apply everything buffered, advancing the watermark."""
        if not self._buffer:
            return 0
        batches, self._buffer = self._buffer, []
        self._buffered = 0
        self._first_buffer_ts = None
        return self._apply(batches)

    def apply_repairs(self, up_paths: Sequence[str],
                      up_fields: Dict[str, np.ndarray],
                      del_paths: Sequence[str], del_uid: np.ndarray,
                      del_gid: np.ndarray, version: int,
                      del_hashes: Optional[np.ndarray] = None
                      ) -> Dict[str, int]:
        """Apply synthetic create/update/delete repair batches from the
        anti-entropy reconciler (core/reconcile.py; DESIGN.md §9.1)
        through the SAME primary-mutation + aggregate-delta path an
        event batch takes, under the shared logical clock: every repair
        carries ``version`` — the changelog seq at the snapshot's scan
        time — so the ``>=`` version gate drops any repair that races a
        fresher event effect (a record the live feed updated after the
        scan keeps its newer value; one it deleted after the scan stays
        dead). Buffered events are flushed first so repairs land on the
        applied state the reconciler diffed. Advances the watermark to
        ``version`` and stamps ``reconciled_at``.

        ``del_uid`` / ``del_gid`` are the owners of the to-be-deleted
        records (read from the index by the reconciler) — the counting
        pipeline's -1 deltas must land on the real principals — and
        ``del_hashes`` their stored FNV hashes, so routing the
        tombstones costs no re-hash.
        """
        self.flush()
        with self._write_lock():
            n_up = len(up_paths)
            up_paths = list(up_paths)
            del_paths = list(del_paths)
            new_mask = self.primary.upsert_batch(
                up_paths, up_fields, np.full(n_up, version, np.int64))
            del_mask = self.primary.delete_batch(
                del_paths, np.full(len(del_paths), version, np.int64),
                hashes=del_hashes)
            up_uid = np.asarray(up_fields["uid"]) if n_up else \
                np.zeros(0, np.int32)
            up_gid = np.asarray(up_fields["gid"]) if n_up else \
                np.zeros(0, np.int32)
            if self.cfg.update_aggregates:
                count_jobs = [(up_paths, up_uid, up_gid, +1.0, new_mask),
                              (del_paths, np.asarray(del_uid, np.int32),
                               np.asarray(del_gid, np.int32), -1.0,
                               del_mask)]
                up_size = (np.asarray(up_fields["size"], np.float32)
                           if n_up else np.zeros(0, np.float32))
                up_mtime = (np.asarray(up_fields["mtime"], np.float32)
                            if n_up else np.zeros(0, np.float32))
                self._apply_aggregates(count_jobs, up_paths, up_uid,
                                       up_gid, up_size, up_mtime,
                                       new_mask)
            if self.hierarchy is not None:
                # repairs are file-grain: mirror-sync both sides through
                # the same probe read-back the event path uses
                self.hierarchy.apply_ops(
                    [("sync", p)
                     for p in dict.fromkeys([*del_paths, *up_paths])],
                    self._probe)
            self.metrics["reconciles"] += 1
            self.metrics["repair_upserts"] += n_up
            self.metrics["repair_tombstones"] += int(del_mask.sum())
            self._advance_watermark(version)
            self.watermark.reconciled_at = self.clock()
            self._notify_applied(int(version), mutated=True)
            return {"upserts": n_up, "tombstones": int(del_mask.sum()),
                    "entered": int(new_mask.sum())}

    def principals_of(self, paths: Sequence[str], uid: np.ndarray,
                      gid: np.ndarray) -> set:
        """Principal slot ids the given records contribute to (uid slot,
        gid slot, dir-prefix slots) — what the reconcile/compaction path
        uses to scope republication."""
        out: set = set()
        if len(paths):
            for pid, w in self._principal_rows(
                    list(paths), np.asarray(uid, np.int32),
                    np.asarray(gid, np.int32))[0]:
                out.update(np.unique(pid[w != 0]).tolist())
        return out

    @property
    def counts_exact(self) -> bool:
        """Whether ``counts`` speaks for the whole index: True unless a
        snapshot handoff (``register_tree``) loaded records this
        ingestor's delta stream never saw and ``seed_counts`` was not
        called. Republication passes exact counts — and therefore drops
        zero-count principals — only when this holds; otherwise a zero
        only means "nothing observed HERE" and must not delete
        snapshot-built summaries."""
        return self._counts_seeded or not self._tree_registered

    def seed_counts(self, counts: np.ndarray) -> None:
        """Seed the (P, S) counting matrix from the snapshot counting
        pipeline's output — the aggregate half of the snapshot -> event
        handoff (``register_tree`` is the primary-index half). After
        seeding, event deltas keep the matrix exact over BOTH
        snapshot-loaded and event-born records, re-arming the
        zero-count ghost-principal drop."""
        counts = np.asarray(counts, np.float32)
        assert counts.shape == self.counts.shape, \
            (counts.shape, self.counts.shape)
        self.counts = counts.copy()
        self._counts_seeded = True

    def _exact_counts(self) -> Optional[np.ndarray]:
        return self.counts.sum(axis=1) if self.counts_exact else None

    def republish(self, principal_ids: Sequence[int]) -> None:
        """Republish the given principals from current sketch state with
        EXACT counts when available (``counts_exact``): principals whose
        live count has dropped to zero are removed from the aggregate
        index instead of lingering as ghosts — the reconcile/compaction
        path's way of flushing dead principals
        (``AggregateIndex.from_sketch_state(only=...)``). No-op when
        aggregate maintenance is disabled."""
        ids = sorted({int(p) for p in principal_ids})
        if not ids or not self.cfg.update_aggregates:
            return
        self.aggregate.from_sketch_state(
            self.pcfg.sketch, self._sketch_state, self._principal_names,
            only=ids, counts=self._exact_counts())

    def freshness(self) -> Dict[str, float]:
        """The watermark readers attach to results (DESIGN.md §6.3).

        ``log_lag`` counts log RECORDS (payloads — micro-batch slices,
        Kafka-style consumer lag, NOT single events like
        ``pending_events``) durably in the log but not yet committed
        behind this ingestor (0 for direct-fed deployments): with
        commit-after-apply it bounds how much replay a crash-restart
        would re-run, and for readers it is the freshness gap BEYOND
        ``pending_events`` — records the broker holds that this index
        has not even buffered yet (DESIGN.md §10.4).

        ``index_lag`` is the discovery-index freshness mark (DESIGN.md
        §11.3): primary mutations not reflected in queryable secondary-
        index state, summed over shards. 0 means the query planner's
        accelerated answers are exact (every apply this ingestor runs
        publishes its touched slots into the discovery delta buffers
        through the primary's version-gated mutation hooks, so the mark
        stays 0 under pure event flow); nonzero means discovery was
        invalidated (bulk snapshot ingest, state restore) and selective
        queries are scanning until a rebuild. Also 0 when no discovery
        index is attached."""
        return {
            "mode": self.cfg.mode,
            "applied_seq": self.watermark.applied_seq,
            "pending_events": self.watermark.pending,
            "staleness_s": max(0.0, self.clock()
                               - self.watermark.last_apply_time),
            "applied_batches": self.watermark.applied_batches,
            "reconciled_at": self.watermark.reconciled_at,
            "log_lag": int(self.lag_source()) if self.lag_source else 0,
            "index_lag": discovery_index_lag(self.primary),
            "rollup_dirty": (self.hierarchy.dirty_count()
                             if self.hierarchy is not None else 0),
            "rollup_exact": (bool(self.hierarchy.exact)
                             if self.hierarchy is not None else False),
        }

    # -- checkpoint / restore (DESIGN.md §10.3) -------------------------------

    def state_dict(self) -> Dict:
        """Serializable ingestor state: the fid-keyed state-manager
        tables, the device sketch state, the exact counting matrix, and
        the watermark. Together with the primary index's ``state_dict``
        this is everything crash recovery needs to resume the stream —
        restore + replay of the post-barrier suffix reproduces the
        uninterrupted run byte-for-byte. Buffered events are NOT
        serialized: callers flush first (the durable pipeline's
        checkpoint barrier is an applied-state barrier)."""
        assert not self._buffer, "flush() before state_dict()"
        return {
            "watermark": {
                "applied_seq": int(self.watermark.applied_seq),
                "applied_batches": int(self.watermark.applied_batches),
                "reconciled_at": float(self.watermark.reconciled_at),
            },
            "metrics": {k: int(v) for k, v in self.metrics.items()},
            "name": {int(k): v for k, v in self._name.items()},
            "parent": {int(k): int(v) for k, v in self._parent.items()},
            "children": {int(k): sorted(int(c) for c in v)
                         for k, v in self._children.items()},
            "stat": {int(k): {kk: (float(vv) if kk not in ("uid", "gid")
                                   else int(vv)) for kk, vv in st.items()}
                     for k, st in self._stat.items()},
            "is_dir": sorted(int(k) for k, v in self._is_dir.items() if v),
            "sketch": {k: pack_array(v)
                       for k, v in self._sketch_state.items()},
            "counts": pack_array(self.counts),
            "counts_seeded": self._counts_seeded,
            "tree_registered": self._tree_registered,
            "hierarchy": (self.hierarchy.state_dict()
                          if self.hierarchy is not None else None),
        }

    def load_state(self, state: Dict) -> None:
        """Restore ``state_dict`` output in place. The ingestor must be
        constructed with the same (cfg, pcfg) shape universe; the
        primary/aggregate indexes are restored separately (they carry
        their own state). Held under the primary write lock so a
        concurrent snapshot never pins a half-restored ingestor."""
        with self._write_lock():
            self._load_state_inner(state)

    def _load_state_inner(self, state: Dict) -> None:
        wm = state["watermark"]
        self.watermark = Watermark(
            applied_seq=int(wm["applied_seq"]),
            applied_batches=int(wm["applied_batches"]),
            reconciled_at=float(wm["reconciled_at"]),
            last_apply_time=self.clock())
        self.metrics.update(state["metrics"])
        self._name = {int(k): v for k, v in state["name"].items()}
        self._parent = {int(k): int(v) for k, v in state["parent"].items()}
        self._children = {int(k): set(v)
                          for k, v in state["children"].items()}
        self._stat = {int(k): dict(st) for k, st in state["stat"].items()}
        self._is_dir = {int(k): True for k in state["is_dir"]}
        self._sketch_state = {k: jnp.asarray(unpack_array(v))
                              for k, v in state["sketch"].items()}
        counts = unpack_array(state["counts"])
        assert counts.shape == self.counts.shape, \
            (counts.shape, self.counts.shape)
        self.counts = counts
        self._counts_seeded = bool(state["counts_seeded"])
        self._tree_registered = bool(state["tree_registered"])
        # restore the rollup tree AFTER the primary's load_state ran
        # (its _mutated(None) invalidated the attached hierarchy; the
        # serialized state re-establishes exactness). A checkpoint that
        # predates rollups restores as invalid -> scan fallback.
        if self.hierarchy is not None:
            self.hierarchy.load_state(state.get("hierarchy"))
        self._buffer, self._buffered = [], 0
        self._first_buffer_ts = None
        # aggregate records are derived state (not serialized):
        # republish every principal from the restored sketch + counts so
        # readers see summaries immediately after a restore
        if self.cfg.update_aggregates:
            self.republish(range(self.pcfg.n_principals))
        # a restore rewinds/replaces readable state wholesale: cached
        # results keyed at any prior watermark are void
        self._notify_applied(int(self.watermark.applied_seq), mutated=True)

    # -- the apply pipeline ---------------------------------------------------

    def _write_lock(self):
        """The primary's MVCC write lock (DESIGN.md §12), or a no-op
        context on duck-typed primaries predating ``write_lock``. Held
        across one WHOLE apply, so a concurrent ``snapshot()`` pins
        batch boundaries only — never a half-applied event batch."""
        wl = getattr(self.primary, "write_lock", None)
        return wl() if wl is not None else contextlib.nullcontext()

    def _notify_applied(self, seq: int, mutated: bool) -> None:
        for cb in self.on_apply:
            cb(seq, mutated)

    # -- subtree-rollup publication (DESIGN.md §14) ---------------------------

    def _probe(self, path: str):
        return self.primary.probe(path)

    def _publish_hierarchy(self, facts, resolve, dead_fids, dead_paths,
                           mv_old, rend_fids, rend_old, up_paths,
                           re_paths) -> None:
        """Emit one applied chunk's rollup ops IN PHASE ORDER:

        1. syncs at OLD keys (deletes + file-rename sources) — before any
           subtree re-key can move the registry entries out from under
           those paths;
        2. whole-subtree moves for renamed dirs — before this batch's
           dir creates, so an ensure-chain can never plant a colliding
           synthetic node at a path a move is about to claim;
        3. dir registrations (alive dirs at their post-fold paths);
        4. rmdirs (dead dirs at their pre-fold paths — a dead dir keeps
           its path mapping for residual-file rollups);
        5. syncs at NEW keys (upserts + both sides of every repath pair
           — the old side backstops version-gate-dropped repaths).

        Every sync probes the primary's post-batch state, so the mirror
        converges on exactly what the version gates actually applied."""
        isdir_of = {int(f): bool(d)
                    for f, d in zip(facts["fid"], facts["is_dir"])}
        ops: List[tuple] = []
        for p in dict.fromkeys([*dead_paths, *mv_old]):
            ops.append(("sync", p))
        moves = [(int(f), old, resolve(int(f)))
                 for f, old in zip(rend_fids, rend_old)]
        if moves:
            # ONE batched op: same-batch move sets can permute arbitrarily
            # (swaps, nested moves), so they detach/attach as a group
            ops.append(("move_dirs", moves))
        live_dirs = facts["is_dir"] & facts["alive"]
        for f in facts["fid"][live_dirs]:
            ops.append(("dir", int(f), resolve(int(f))))
        for f, p in zip(dead_fids, dead_paths):
            if isdir_of.get(int(f)):
                ops.append(("rmdir", int(f), p))
        re_old = re_paths.get("old", []) if re_paths else []
        re_new = re_paths.get("new", []) if re_paths else []
        for p in dict.fromkeys([*up_paths, *re_old, *re_new]):
            ops.append(("sync", p))
        self.hierarchy.apply_ops(ops, self._probe)

    def _seed_hierarchy(self) -> None:
        """Rebuild the rollup tree from the registered fid tree + the
        primary's live view — the snapshot handoff's hierarchy half
        (register_tree is the resolver half, seed_counts the aggregate
        half). Restores ``exact`` after bulk ingest invalidation."""
        h = self.hierarchy
        if h is None:
            return
        dir_fids = sorted({f for f, d in self._is_dir.items() if d}
                          | {p for p in self._parent.values() if p >= 0})
        try:
            paths = resolve_paths_host(self._parent, self._name, dir_fids)
        except ValueError:               # cycle/overflow: corrupt tree
            h.invalidate()
            return
        pairs = [(f, p) for f, p in zip(dir_fids, paths) if p is not None]
        h.seed(pairs, self.primary.live())

    def _apply(self, batches: List[Dict[str, np.ndarray]]) -> int:
        t0 = self.telemetry.clock()
        with self._write_lock():
            n = self._apply_inner(batches)
        self._h_apply_s.observe(self.telemetry.clock() - t0)
        return n

    def _apply_inner(self, batches: List[Dict[str, np.ndarray]]) -> int:
        b = {k: np.concatenate([np.asarray(bb[k]) for bb in batches])
             for k in batches[0]}
        n_in = len(b["fid"])
        if self.telemetry.enabled and n_in:
            self.telemetry.event_stage("apply", int(b["seq"].max()))

        facts = self._coalesce(b)
        if facts is None:
            # nothing survived coalescing (e.g. all-OPEN with filtering
            # on): the watermark advances, the readable state does not
            seq = int(b["seq"].max())
            self._advance_watermark(seq)
            self._notify_applied(seq, mutated=False)
            return n_in

        # a fid the state manager knows as a directory stays one even when
        # this batch's events omit the flag (e.g. a bare RENME on a dir)
        facts["is_dir"] |= np.fromiter(
            (self._is_dir.get(int(f), False) for f in facts["fid"]),
            bool, len(facts["fid"]))

        # rename override: snapshot OLD paths of live descendants BEFORE
        # the fact fold moves the subtree (paper §IV-B2 rule 3)
        ren_dirs_sel = facts["renamed"] & facts["is_dir"]
        old_desc = self._live_descendant_paths(
            facts["fid"][ren_dirs_sel], facts["seq"][ren_dirs_sel])
        # stats + subjects of to-be-deleted fids, read before the fold:
        # the tombstone must hit the path the record is indexed under
        # (pre-rename), and the counting decrement needs the old slots
        dead = facts["dead"]
        dead_fids = facts["fid"][dead]
        pre_resolve = self._make_resolver()
        dead_paths = [pre_resolve(int(f)) for f in dead_fids]
        # owner of the dying record: state-manager stat, else the indexed
        # record itself (register_tree handoff), else zeros
        dead_prev = [self._stat.get(int(f)) or self._record_fields(p) or {}
                     for f, p in zip(dead_fids, dead_paths)]
        # first event for a fid the snapshot indexed (register_tree
        # handoff): seed its stat from the record so sparse events merge
        # onto the scanned values instead of zeros
        for f in facts["fid"][facts["alive"] & ~facts["created"]]:
            fi = int(f)
            if fi not in self._stat and fi in self._parent:
                rec = self._record_fields(pre_resolve(fi))
                if rec:
                    self._stat[fi] = rec
        # ownership facts on already-known records: capture the
        # pre-batch owner BEFORE the fold, so a chown MOVES the count
        # between principals (the enter/leave deltas alone would strand
        # it on the old owner — and, worse, drive the old owner's exact
        # count to zero and ghost-drop a still-live principal)
        own_rows = np.nonzero((facts["has_uid"] | facts["has_gid"])
                              & facts["alive"] & ~facts["created"]
                              & ~facts["is_dir"])[0]
        pre_own: Dict[int, tuple] = {}
        for i in own_rows:
            fi = int(facts["fid"][i])
            st = self._stat.get(fi)
            if st is not None:
                pre_own[fi] = (int(st.get("uid", 0)),
                               int(st.get("gid", 0)))
        # FILE renames move a single subject: remember the old path now,
        # tombstone it after the fold (dir renames go via old_desc)
        ren_files = facts["renamed"] & ~facts["is_dir"] & facts["alive"]
        renf_fids = facts["fid"][ren_files]
        renf_old = [pre_resolve(int(f)) for f in renf_fids]
        renf_seq = facts["seq"][ren_files]
        # rollup moves need the renamed dirs' OWN old paths (pre-fold);
        # dirs also created this batch never existed at an old path
        ren_moved = ren_dirs_sel & facts["alive"] & ~facts["created"]
        rend_fids = facts["fid"][ren_moved]
        rend_old = [pre_resolve(int(f)) for f in rend_fids]

        self._fold_facts(facts)

        # resolve live subjects AFTER the fold (paths reflect the new tree)
        resolve = self._make_resolver()
        up = facts["alive"] & ~facts["is_dir"]
        up_fids = facts["fid"][up]
        up_paths = [resolve(int(f)) for f in up_fids]
        up_vers = facts["seq"][up].copy()
        # chunk-invariant versions: a subject under a dir renamed IN THIS
        # batch carries the rename's seq when newer than its own last
        # event — exactly the version the repath override would stamp if
        # the rename had arrived in a later batch. Without this, the
        # durable pipeline's replay (which re-chunks the stream) could
        # recover records at different versions than the uninterrupted
        # run (DESIGN.md §10.2).
        ren_seq_of = {int(f): int(s) for f, s in
                      zip(facts["fid"][ren_dirs_sel],
                          facts["seq"][ren_dirs_sel])}
        if ren_seq_of:
            memo_rs: Dict[int, int] = {}

            def anc_rename_seq(d: int) -> int:
                chain = []
                best = 0
                on_walk = set()
                while d >= 0 and d not in memo_rs and d not in on_walk:
                    on_walk.add(d)
                    chain.append(d)
                    d = self._parent.get(d, -1)
                best = memo_rs.get(d, 0) if d >= 0 else 0
                for c in reversed(chain):
                    best = max(best, ren_seq_of.get(c, 0))
                    memo_rs[c] = best
                return best

            for i, f in enumerate(up_fids):
                rs = anc_rename_seq(self._parent.get(int(f), -1))
                if rs > up_vers[i]:
                    up_vers[i] = rs
        # columns from the MERGED fact tables (a sparse batch inherits the
        # fields it didn't carry from earlier events / the stored record)
        up_stats = [self._stat.get(int(f), {}) for f in up_fids]
        up_uid = np.array([s.get("uid", 0) for s in up_stats], np.int32)
        up_gid = np.array([s.get("gid", 0) for s in up_stats], np.int32)
        up_size = np.array([s.get("size", 0.0) for s in up_stats],
                           np.float32)
        up_mtime = np.array([s.get("mtime", 0.0) for s in up_stats],
                            np.float32)

        dead_in_batch = frozenset(
            int(f) for f in facts["fid"][facts["dead"] | facts["cancelled"]])
        re_paths, re_fields = self._repath(old_desc, resolve, dead_in_batch)

        # primary index: vectorized columnar upserts + tombstones
        fields = {
            "path_hash": np.array([md.path_hash(p) for p in up_paths],
                                  np.uint32),
            "type": np.full(len(up_paths), md.TYPE_FILE, np.int32),
            "uid": up_uid,
            "gid": up_gid,
            "size": up_size,
            "mtime": up_mtime,
            "atime": up_mtime,
            "ctime": up_mtime,
        }
        new_mask = self.primary.upsert_batch(up_paths, fields, up_vers)
        count_jobs = [(up_paths, up_uid, up_gid, +1.0, new_mask)]
        # chown on a record that stayed live: -1 at the old principal
        # streams, +1 at the new (the dir-prefix components cancel
        # exactly, so only the uid/gid principals actually move)
        moved_own = [i for i, f in enumerate(up_fids)
                     if int(f) in pre_own and not new_mask[i]
                     and (int(up_uid[i]), int(up_gid[i]))
                     != pre_own[int(f)]]
        if moved_own:
            mv_paths = [up_paths[i] for i in moved_own]
            sel = np.ones(len(moved_own), bool)
            count_jobs.append((
                mv_paths,
                np.array([pre_own[int(up_fids[i])][0]
                          for i in moved_own], np.int32),
                np.array([pre_own[int(up_fids[i])][1]
                          for i in moved_own], np.int32),
                -1.0, sel))
            count_jobs.append((mv_paths, up_uid[moved_own],
                               up_gid[moved_own], +1.0, sel))
        if re_paths:
            re_vers = np.asarray(re_paths["vers"], np.int64)
            re_new = self.primary.upsert_batch(re_paths["new"], re_fields,
                                               re_vers)
            re_dead = self.primary.delete_batch(re_paths["old"], re_vers)
            count_jobs.append((re_paths["new"], re_fields["uid"],
                               re_fields["gid"], +1.0, re_new))
            count_jobs.append((re_paths["old"], re_fields["uid"],
                               re_fields["gid"], -1.0, re_dead))
            self.metrics["repathed"] += len(re_paths["new"])
        del_mask = self.primary.delete_batch(dead_paths, facts["seq"][dead])
        if len(dead_paths):
            uidd = np.array([s.get("uid", 0) for s in dead_prev], np.int32)
            gidd = np.array([s.get("gid", 0) for s in dead_prev], np.int32)
            count_jobs.append((dead_paths, uidd, gidd, -1.0, del_mask))
        # file-rename tombstones: old subject dies at the rename's seq
        moved = [i for i, (f, o) in enumerate(zip(renf_fids, renf_old))
                 if resolve(int(f)) != o]
        mv_old: List[str] = []
        if moved:
            mv_old = [renf_old[i] for i in moved]
            mv_stats = [self._stat.get(int(renf_fids[i]))
                        or self._record_fields(renf_old[i]) or {}
                        for i in moved]
            mv_dead = self.primary.delete_batch(
                mv_old, renf_seq[moved])
            count_jobs.append((
                mv_old,
                np.array([s.get("uid", 0) for s in mv_stats], np.int32),
                np.array([s.get("gid", 0) for s in mv_stats], np.int32),
                -1.0, mv_dead))
            self.metrics["repathed"] += len(mv_old)

        if self.hierarchy is not None:
            self._publish_hierarchy(facts, resolve, dead_fids, dead_paths,
                                    mv_old, rend_fids, rend_old, up_paths,
                                    re_paths)

        if self.cfg.update_aggregates:
            self._apply_aggregates(count_jobs, up_paths, up_uid, up_gid,
                                   up_size, up_mtime, new_mask)

        self.metrics["applied"] += n_in
        self.metrics["upserts"] += len(up_paths)
        self.metrics["tombstones"] += int(del_mask.sum())
        self.metrics["cancelled"] += int(facts["cancelled"].sum())
        self.metrics["applies"] += 1
        seq = int(b["seq"].max())
        self._advance_watermark(seq)
        self._notify_applied(seq, mutated=True)
        return n_in

    def _advance_watermark(self, seq: int) -> None:
        self.watermark.applied_seq = max(self.watermark.applied_seq, seq)
        self.watermark.pending = self._buffered
        self.watermark.last_apply_time = self.clock()
        self.watermark.applied_batches += 1
        self._g_applied_seq.set(self.watermark.applied_seq)
        self._g_pending.set(self.watermark.pending)
        self.telemetry.event_visible(self.watermark.applied_seq)

    def _coalesce(self, b: Dict[str, np.ndarray]) -> Optional[Dict]:
        """Rules 1+2 on the host: last event per fid is its representative;
        per-fid facts via last-write-wins scatters over the (fid, seq)
        sorted view. Returns per-UNIQUE-fid arrays."""
        etype = b["etype"]
        valid = np.ones(len(etype), bool)
        if self.cfg.filter_opens:
            valid &= etype != ev.E_OPEN
        if not valid.any():
            return None
        b = {k: v[valid] for k, v in b.items()}
        order = np.lexsort((b["seq"], b["fid"]))
        b = {k: v[order] for k, v in b.items()}
        fid = b["fid"]
        etype = b["etype"]
        uf, inv = np.unique(fid, return_inverse=True)
        m = len(uf)

        def last(values, mask=None, init=0):
            out = np.full(m, init, np.asarray(values).dtype)
            if mask is None:
                out[inv] = values           # sorted by seq -> last wins
            else:
                out[inv[mask]] = values[mask]
            return out

        last_et = last(etype)
        seq = last(b["seq"])
        created = np.zeros(m, bool)
        np.logical_or.at(created, inv,
                         (etype == ev.E_CREAT) | (etype == ev.E_MKDIR))
        renamed = np.zeros(m, bool)
        np.logical_or.at(renamed, inv, etype == ev.E_RENME)
        is_dir = np.zeros(m, bool)
        np.logical_or.at(is_dir, inv, b["is_dir"] > 0)

        parent_eff = np.where(b["new_parent_fid"] >= 0,
                              b["new_parent_fid"], b["parent_fid"])
        parent = last(parent_eff, parent_eff >= 0, init=-1)
        # stat facts: stat-carrying rows win; else the last row that
        # carried a nonzero value (Lustre events are stat-free, so e.g. an
        # UNLNK row's zero uid must not clobber the CREAT's)
        hs = b["has_stat"] > 0
        any_stat = np.zeros(m, bool)
        np.logical_or.at(any_stat, inv, hs)    # ANY row, not just the last

        def any_pos(field):
            out = np.zeros(m, bool)
            np.logical_or.at(out, inv, b[field] > 0)
            return out

        def fact(field):
            v = b[field]
            return np.where(any_stat, last(v, hs), last(v, v > 0))

        size = fact("size")
        mtime = fact("mtime")
        # ownership: stat rows may omit uid/gid (e.g. a bare WRITE), so a
        # chown is whichever row last carried a nonzero owner
        uid = last(b["uid"], b["uid"] > 0)
        gid = last(b["gid"], b["gid"] > 0)
        # which facts this batch actually carried (events are sparse: a
        # batch with no stat/owner info must not clobber stored facts)
        has_size = any_stat | any_pos("size")
        has_mtime = any_stat | any_pos("mtime")
        has_uid = any_pos("uid")
        has_gid = any_pos("gid")

        is_del = (last_et == ev.E_UNLNK) | (last_et == ev.E_RMDIR)
        cancelled = is_del & created
        return {
            "fid": uf, "seq": seq, "parent": parent,
            "size": size, "mtime": mtime, "uid": uid, "gid": gid,
            "is_dir": is_dir, "renamed": renamed, "created": created,
            "alive": ~is_del, "dead": is_del & ~created,
            "cancelled": cancelled,
            "has_stat": any_stat,
            "has_size": has_size, "has_mtime": has_mtime,
            "has_uid": has_uid, "has_gid": has_gid,
        }

    def _fold_facts(self, facts: Dict) -> None:
        """Apply coalesced facts to the host fid tables (the paper's state
        manager; dict ops only — O(unique fids))."""
        for i, f in enumerate(facts["fid"]):
            f = int(f)
            if facts["dead"][i] or facts["cancelled"][i]:
                self._stat.pop(f, None)
                old_p = self._parent.get(f)
                if old_p is not None:
                    self._children.get(old_p, set()).discard(f)
                continue
            p = int(facts["parent"][i])
            if p >= 0:
                old_p = self._parent.get(f)
                if old_p is not None and old_p != p:
                    self._children.get(old_p, set()).discard(f)
                self._parent[f] = p
                self._children.setdefault(p, set()).add(f)
            if facts["is_dir"][i]:
                self._is_dir[f] = True
            st = self._stat.setdefault(
                f, {"size": 0.0, "mtime": 0.0, "uid": 0, "gid": 0})
            if facts["has_size"][i]:
                st["size"] = float(facts["size"][i])
            if facts["has_mtime"][i]:
                st["mtime"] = float(facts["mtime"][i])
                # snapshot-seeded access times are stale once an event
                # touches the record; drop them so downstream writers
                # fall back to the atime=ctime=mtime event convention
                st.pop("atime", None)
                st.pop("ctime", None)
            if facts["has_uid"][i]:
                st["uid"] = int(facts["uid"][i])
            if facts["has_gid"][i]:
                st["gid"] = int(facts["gid"][i])

    def _make_resolver(self) -> Callable[[int], str]:
        memo: Dict[int, str] = {}

        def resolve(f: int) -> str:
            # iterative parent walk: collect the unmemoized ancestor
            # chain, then fill memo root-to-leaf (no recursion cap, so
            # legitimately deep trees resolve; only a TRUE parent cycle
            # — corrupt changelog, a real FS rejects subtree-into-itself
            # renames — anchors at a loud marker instead of looping)
            chain = []
            on_walk = set()
            cur = f
            while True:
                got = memo.get(cur)
                if got is not None:
                    prefix = got
                    break
                if cur in on_walk:
                    self.metrics["unresolved"] += 1
                    prefix = f"/#cycle#{cur}"
                    break
                on_walk.add(cur)
                name = self._name.get(cur)
                if name is None:
                    # fid never registered (e.g. scanned by a snapshot
                    # before this ingestor attached): subjects resolved
                    # through this fallback cannot match the snapshot-
                    # loaded record — count it loudly; deployments
                    # should register_tree() first
                    self.metrics["unresolved"] += 1
                    name = f"#{cur}"
                chain.append((cur, name))
                p = self._parent.get(cur, -1)
                if p < 0:
                    prefix = ""
                    break
                cur = p
            for fid, name in reversed(chain):
                prefix = prefix + "/" + name
                memo[fid] = prefix
            return memo[f] if chain else prefix
        return resolve

    def register_tree(self, parents: Dict[int, int], names: Dict[int, str],
                      is_dir: Optional[Dict[int, bool]] = None) -> None:
        """Bootstrap the state manager with an existing fid -> (parent,
        name) tree — the snapshot -> event handoff (paper §IV-B3: the
        scanner records fids, so a changelog event on a pre-scan file
        resolves to the same subject the snapshot indexed). Without this,
        events for unknown fids resolve to '#fid' fallback subjects and
        cannot touch snapshot-loaded records (metrics['unresolved']).
        Pair with ``seed_counts`` to keep the aggregate counting matrix
        exact over the snapshot-loaded records too (``counts_exact``)."""
        self._tree_registered = True
        self._name.update(names)
        for f, p in parents.items():
            self._parent[f] = p
            self._children.setdefault(p, set()).add(f)
        for f, d in (is_dir or {}).items():
            if d:
                self._is_dir[f] = True
        # the hierarchy half of the handoff: re-seed the rollup tree
        # from the registered dirs + the primary's live records (the
        # bulk snapshot ingest just invalidated it)
        self._seed_hierarchy()

    def _live_descendant_paths(self, dir_fids: np.ndarray,
                               dir_seqs: np.ndarray
                               ) -> Dict[int, Tuple[str, int]]:
        """Old subjects of every FILE under the given renamed dirs,
        resolved against the pre-rename tree, each tagged with the seq
        of the rename that moves it (the max over its renamed ancestors
        — that PER-EVENT seq is the repath's version, so replaying the
        same events in different batch groupings lands identical
        versions: the durable pipeline's chunk-invariance contract,
        DESIGN.md §10.2). Includes files known only through
        ``register_tree`` (no event-derived stat yet) — their index
        record is the source of truth at repath time."""
        if len(dir_fids) == 0:
            return {}
        resolve = self._make_resolver()
        out: Dict[int, Tuple[str, int]] = {}
        stack = [(int(f), int(s)) for f, s in zip(dir_fids, dir_seqs)]
        seen: Dict[int, int] = {}
        while stack:
            d, seq = stack.pop()
            if seen.get(d, -1) >= seq:
                continue
            seen[d] = seq
            for c in self._children.get(d, ()):
                if self._is_dir.get(c):
                    stack.append((c, seq))
                else:
                    got = out.get(c)
                    out[c] = (resolve(c) if got is None else got[0],
                              seq if got is None else max(got[1], seq))
        return out

    def _record_fields(self, path: str) -> Optional[Dict[str, float]]:
        """Owner/stat of the indexed record at ``path`` (live or not) —
        the fallback fact source for fids the state manager only knows
        via register_tree. Routes through the index's ``get_record`` so
        sharded primaries resolve it in the owning shard. Includes
        atime/ctime so a repath can move a snapshot-loaded record
        without zeroing its access times."""
        return self.primary.get_record(
            path, keys=("uid", "gid", "size", "mtime", "atime", "ctime"))

    def _repath(self, old_desc: Dict[int, Tuple[str, int]],
                resolve: Callable[[int], str],
                dead_in_batch: frozenset):
        """Rename override on the index: move descendants whose subject
        changed (old tombstone + new upsert carrying the stored stat, or
        the indexed record's own fields for register_tree-only fids).
        Each move carries the triggering rename's OWN seq as its version
        (``old_desc`` values are (old_path, rename_seq))."""
        if not old_desc:
            return {}, {}
        olds, news, stats, vers = [], [], [], []
        for f, (old_path, seq) in old_desc.items():
            if f in dead_in_batch:      # deleted in this same batch
                continue
            st = self._stat.get(f) or self._record_fields(old_path)
            if st is None:              # never indexed, nothing to move
                continue
            new_path = resolve(f)
            if new_path == old_path:
                continue
            olds.append(old_path)
            news.append(new_path)
            stats.append(st)
            vers.append(seq)
        if not news:
            return {}, {}
        mtimes = np.array([s.get("mtime", 0.0) for s in stats], np.float32)
        fields = {
            "path_hash": np.array([md.path_hash(p) for p in news], np.uint32),
            "type": np.full(len(news), md.TYPE_FILE, np.int32),
            "uid": np.array([s.get("uid", 0) for s in stats], np.int32),
            "gid": np.array([s.get("gid", 0) for s in stats], np.int32),
            "size": np.array([s.get("size", 0.0) for s in stats], np.float32),
            "mtime": mtimes,
            # a repath moves the record, it does not touch it: carry the
            # stored access times (event-derived records fall back to the
            # mtime convention, DESIGN.md §6.2)
            "atime": np.array([s.get("atime", s.get("mtime", 0.0))
                               for s in stats], np.float32),
            "ctime": np.array([s.get("ctime", s.get("mtime", 0.0))
                               for s in stats], np.float32),
        }
        return {"old": olds, "new": news, "vers": vers}, fields

    # -- aggregate pipeline (device) -----------------------------------------

    def _principal_rows(self, paths: List[str],
                        uid: np.ndarray, gid: np.ndarray):
        """(streams, sids): principal slot streams exactly like snapshot
        preprocessing — uid slot, gid slot, and one dir-prefix slot per
        depth in [dir_min, dir_max] (slot = FNV hash of the ancestor dir's
        path, computed from the resolved parent chain)."""
        cfg = self.pcfg
        n = len(paths)
        uid_slot = uid.astype(np.int64) % cfg.n_users
        gid_slot = cfg.n_users + gid.astype(np.int64) % cfg.n_groups
        base = cfg.n_users + cfg.n_groups
        levels = cfg.dir_max - cfg.dir_min + 1
        dir_slots = np.full((n, levels), -1, np.int64)
        memo: Dict[str, np.ndarray] = {}
        for i, p in enumerate(paths):
            dpath = p.rsplit("/", 1)[0]
            got = memo.get(dpath)
            if got is None:
                comps = [c for c in dpath.split("/") if c]
                got = np.full(levels, -1, np.int64)
                for li, depth in enumerate(range(cfg.dir_min,
                                                 cfg.dir_max + 1)):
                    if depth < len(comps):
                        anc = "/" + "/".join(comps[:depth + 1])
                        got[li] = base + md.path_hash(anc) % cfg.n_dirs
                memo[dpath] = got
            dir_slots[i] = got
        sids = np.fromiter((md.crc32_shard(p.encode(), cfg.n_shards)
                            for p in paths), np.int64, n)
        streams = [(uid_slot, np.ones(n, np.float32)),
                   (gid_slot, np.ones(n, np.float32))]
        for li in range(levels):
            pid = dir_slots[:, li]
            streams.append((np.maximum(pid, 0),
                            (pid >= 0).astype(np.float32)))
        return streams, sids

    def _apply_aggregates(self, count_jobs, up_paths, up_uid, up_gid,
                          up_size, up_mtime, new_mask) -> None:
        """Device-side aggregate maintenance for one applied batch: counting
        deltas (±1 per subject entering/leaving the index, including
        rename moves between dir principals) and sketch observations for
        newly-seen subjects, then republish touched principals."""
        cfg = self.pcfg
        touched: set = set()

        for paths, uid, gid, sign, sel in count_jobs:
            if not np.any(sel):
                continue
            paths = [p for p, s in zip(paths, sel) if s]
            streams, sids = self._principal_rows(paths, uid[sel], gid[sel])
            pid_cat = np.concatenate([p for p, _ in streams])
            w_cat = np.concatenate([w for _, w in streams]) * sign
            sid_cat = np.tile(sids, len(streams))
            npad = _bucket(len(pid_cat), self.cfg.pad_to)
            delta = self._count_step(
                jnp.asarray(_pad(pid_cat, npad)),
                jnp.asarray(_pad(sid_cat, npad)),
                jnp.asarray(_pad(w_cat, npad)))
            self.counts += np.asarray(delta, np.float32)
            touched.update(np.unique(pid_cat[w_cat != 0]).tolist())

        # sketch observations: once per newly-seen subject (additive-only;
        # updates/deletes reach quantiles at the next snapshot rebuild)
        sel = new_mask
        if np.any(sel):
            paths = [p for p, s in zip(up_paths, sel) if s]
            streams, _ = self._principal_rows(paths, up_uid[sel],
                                              up_gid[sel])
            mt = up_mtime[sel]
            vals = np.stack([up_size[sel],
                             mt, mt, mt])          # size, atime, ctime, mtime
            pid_cat = np.concatenate([p for p, _ in streams])
            w_cat = np.concatenate([w for _, w in streams])
            vals_cat = np.tile(vals, (1, len(streams)))
            npad = _bucket(len(pid_cat), self.cfg.pad_to)
            vals_p = np.stack([_pad(vals_cat[a], npad)
                               for a in range(vals_cat.shape[0])])
            self._sketch_state = _sketch_apply(
                cfg.sketch, self._sketch_state, jnp.asarray(vals_p),
                jnp.asarray(_pad(pid_cat, npad).astype(np.int32)),
                jnp.asarray(_pad(w_cat, npad)))
            self.metrics["sketch_rows"] += int(w_cat.sum())
            touched.update(np.unique(pid_cat[w_cat != 0]).tolist())

        if touched:
            # exact counts (when the matrix speaks for the whole index,
            # see counts_exact) override the sketch's additive-only
            # count, so a principal whose last record died in this batch
            # is dropped from the aggregate index, not left as a ghost
            self.aggregate.from_sketch_state(
                cfg.sketch, self._sketch_state, self._principal_names,
                only=sorted(int(t) for t in touched),
                counts=self._exact_counts())

    def _count_step(self, pids, sids, weights):
        seg = seg_ops.segstats(pids.astype(jnp.int32), sids.astype(jnp.int32),
                               weights, weights, self.pcfg.n_principals,
                               self.pcfg.n_shards)
        return seg["counts"]
