"""Anti-entropy reconciliation + tombstone compaction (core/reconcile.py;
ISSUE 3 tentpole, DESIGN.md §9).

Contracts pinned here:

- reconcile converges a drifted index (missing / stale / extra records)
  to a fresh snapshot's state, per shard, writing only drifted rows;
- the ``>=`` version gate protects records the live feed touched after
  the scan (repairing is safe to race with ingestion);
- through the ingestor, repairs advance the watermark, stamp
  ``reconciled_at``, and keep the aggregate counting matrix exact;
- compaction reclaims tombstoned slots without changing any observable
  state (live rows, column values, versions, watermark), across both
  SlotMap implementations, and drops ghost principals from the
  aggregate index on republication.

The end-to-end dropped-events legs live in tests/test_differential.py.
"""
import numpy as np
import pytest

from repro.core import events as ev
from repro.core import snapshot as snap
from repro.core.event_ingest import EventIngestor, IngestConfig
from repro.core.index import AggregateIndex, DictSlotMap, PrimaryIndex
from repro.core.metadata import (MetadataTable, files_only, path_hash,
                                 synth_filesystem)
from repro.core.query import QueryEngine
from repro.core.reconcile import (ReconcileReport, compact_if_needed,
                                  reconcile)
from repro.core.sharded_index import ShardedPrimaryIndex

PCFG = snap.PipelineConfig(n_users=8, n_groups=4, n_dirs=16)


def make_primary(n_shards):
    return (PrimaryIndex() if n_shards is None
            else ShardedPrimaryIndex(n_shards))


def sorted_live(idx):
    live = idx.live()
    order = np.argsort(live["path"])
    return {k: v[order] for k, v in live.items()}


def assert_same_live(a, b, ctx=""):
    la, lb = sorted_live(a), sorted_live(b)
    assert set(la) == set(lb), ctx
    for k in la:
        assert np.array_equal(la[k], lb[k]), (ctx, k)


def tiny_table(paths, sizes, uid=3, gid=1, mtime=5.0):
    n = len(paths)
    paths = np.asarray(paths, object)
    z = np.zeros(n, np.int32)
    t = np.full(n, mtime)
    return MetadataTable(
        paths=paths,
        path_hash=np.array([path_hash(p) for p in paths], np.uint32),
        parent=np.zeros(n, np.int64), depth=z, type=z, mode=z,
        uid=np.full(n, uid, np.int32), gid=np.full(n, gid, np.int32),
        size=np.asarray(sizes, float), atime=t, ctime=t, mtime=t,
        fileset=z)


# ---------------------------------------------------------------------------
# reconcile: diff + repair on a drifted index
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_shards", [None, 1, 4])
def test_reconcile_converges_drifted_index(n_shards):
    """Missing records (dropped creates), stale columns (dropped
    updates), and extra records (dropped deletes) all converge to the
    snapshot; the result is byte-identical to a from-scratch rebuild
    and the report tallies each drift class."""
    files = files_only(synth_filesystem(2000, n_dirs=80, seed=5))
    n = len(files)
    rng = np.random.default_rng(5)
    gone = np.zeros(n, bool)
    gone[rng.choice(n, size=40, replace=False)] = True
    truth = files.select(~gone)              # 40 deletes the feed dropped
    truth.size[:25] = truth.size[:25] * 2 + 1.0   # 25 dropped updates
    surv = np.nonzero(~gone)[0]
    missing = np.zeros(n, bool)
    missing[surv[-30:]] = True               # 30 dropped creates
    drifted_load = files.select(~missing)
    idx = make_primary(n_shards)
    idx.ingest_table(drifted_load, 1)

    rep = reconcile(truth, version=2, primary=idx)
    rebuilt = make_primary(n_shards)
    rebuilt.ingest_table(truth, 1)
    assert_same_live(idx, rebuilt, f"shards={n_shards}")
    assert rep.checked == len(truth)
    assert (rep.creates, rep.updates, rep.deletes) == (30, 25, 40)
    assert rep.applied_upserts == rep.creates + rep.updates
    assert rep.applied_tombstones == 40
    assert rep.shards == (n_shards or 1)

    # a second pass over an already-converged index is a no-op
    rep2 = reconcile(truth, version=3, primary=idx)
    assert rep2.repairs == 0 and rep2.applied_upserts == 0


def test_reconcile_identical_snapshot_writes_nothing():
    files = files_only(synth_filesystem(500, n_dirs=40, seed=1))
    idx = PrimaryIndex()
    idx.ingest_table(files, 1)
    versions_before = idx.version[:len(idx.slot_map)].copy()
    rep = reconcile(files, version=9, primary=idx)
    assert rep.repairs == 0
    # zero repairs means zero writes: stored versions untouched
    np.testing.assert_array_equal(
        idx.version[:len(idx.slot_map)], versions_before)


def test_reconcile_version_gate_protects_fresher_records():
    """Repairs lose the version race by design: a record the live feed
    created/updated/deleted AFTER the scan keeps its fresher state even
    though the (older) snapshot disagrees."""
    idx = PrimaryIndex()
    idx.ingest_table(tiny_table(["/fs/a", "/fs/b"], [1.0, 2.0]), 5)
    # after the scan (seq > 5): /fs/a updated, /fs/b deleted, /fs/c born
    idx.upsert_batch(["/fs/a"], {
        "path_hash": np.array([path_hash("/fs/a")], np.uint32),
        "size": np.array([99.0], np.float32)}, np.array([10]))
    idx.delete_batch(["/fs/b"], np.array([11]))
    idx.upsert_batch(["/fs/c"], {
        "path_hash": np.array([path_hash("/fs/c")], np.uint32),
        "size": np.array([7.0], np.float32)}, np.array([12]))
    rep = reconcile(tiny_table(["/fs/a", "/fs/b"], [1.0, 2.0]),
                    version=5, primary=idx)
    # the diff flags all three, but every repair is version-gated out
    assert rep.updates == 1 and rep.creates == 1 and rep.deletes == 1
    assert rep.applied_tombstones == 0
    live = sorted_live(idx)
    assert list(live["path"]) == ["/fs/a", "/fs/c"]
    assert float(idx.lookup("/fs/a")["size"]) == 99.0
    assert idx.lookup("/fs/b") is None


def test_reconcile_through_ingestor_watermark_and_counts():
    """Routed through the ingestor, repairs advance the shared
    watermark, stamp ``reconciled_at``, and keep the delta-maintained
    counting matrix equal to a from-scratch reference over the live
    index — including the -1 deltas of repair tombstones."""
    from test_event_ingest import reference_counts
    prim, agg = PrimaryIndex(), AggregateIndex()
    t = {"now": 100.0}
    ing = EventIngestor(IngestConfig(pad_to=64),
                        event_pcfg(), prim, agg, names={0: "fs"},
                        clock=lambda: t["now"])
    s = ev.EventStream(start_fid=1)
    fids = []
    for i in range(8):
        f = s.alloc_fid()
        s.emit(ev.E_CREAT, f, 0, has_stat=1, size=100.0 * (i + 1),
               mtime=5.0, uid=3, gid=1, name=f"f{f}")
        fids.append(f)
    ing.ingest(s.take(), names=s.names)
    # scan truth: f1..f5 live with doubled sizes, f6..f8 deleted, g1 new
    live_paths = [f"/fs/f{f}" for f in fids]
    truth = tiny_table(live_paths[:5] + ["/fs/g1"],
                       [200.0 * (i + 1) for i in range(5)] + [42.0])
    rep = reconcile(truth, version=50, ingestor=ing)
    assert rep.applied_tombstones == 3 and rep.applied_upserts == 6
    assert sorted(prim.live()["path"]) == sorted(truth.paths)
    fr = ing.freshness()
    assert fr["applied_seq"] == 50
    assert fr["reconciled_at"] == 100.0
    assert ing.metrics["reconciles"] == 1
    np.testing.assert_allclose(ing.counts, reference_counts(prim))
    # QueryEngine surfaces the reconcile mark next to results
    q = QueryEngine(prim, agg, ingestor=ing)
    assert q.query("find_by_name", "g1")["freshness"]["reconciled_at"] \
        == 100.0


def event_pcfg():
    from repro.core.sketches.ddsketch import DDSketchConfig
    return snap.PipelineConfig(
        n_users=8, n_groups=4, n_dirs=20,
        sketch=DDSketchConfig(alpha=0.05, n_buckets=512, offset=32))


# ---------------------------------------------------------------------------
# ghost principals (ISSUE 3 satellite): delete-everything regression
# ---------------------------------------------------------------------------

def test_delete_everything_drops_ghost_principals():
    """Principals whose last record is deleted must vanish from
    AggregateIndex.records — directories_over / per_user_usage must not
    report ghost directories/users."""
    prim, agg = PrimaryIndex(), AggregateIndex()
    ing = EventIngestor(IngestConfig(pad_to=64), event_pcfg(), prim, agg,
                        names={0: "fs"})
    s = ev.EventStream(start_fid=1)
    d = s.alloc_fid()
    s.emit(ev.E_MKDIR, d, 0, is_dir=1, name=f"d{d}")
    fids = []
    for i in range(5):
        f = s.alloc_fid()
        s.emit(ev.E_CREAT, f, d, has_stat=1, size=10.0, mtime=1.0,
               uid=3, gid=1, name=f"f{f}")
        fids.append(f)
    ing.ingest(s.take(), names=s.names)
    q = QueryEngine(prim, agg, ingestor=ing)
    assert agg.get("user:3")["file_count"] == 5
    assert q.per_user_usage().get("user:3", (0, 0))[1] == 5
    assert len(q.directories_over(0)) > 0
    for f in fids:
        s.emit(ev.E_UNLNK, f, d)
    ing.ingest(s.take())
    assert len(prim) == 0
    assert agg.get("user:3") is None, "ghost user summary"
    assert q.per_user_usage() == {}
    assert q.directories_over(0) == [], "ghost directories"


def test_unseeded_snapshot_handoff_must_not_drop_principals():
    """Regression: after a snapshot handoff (register_tree) the
    ingestor's delta counts do NOT speak for the snapshot-loaded
    records. The first delete event must not pop the snapshot-built
    summary (counts go negative only in the delta view); after
    seed_counts with the true matrix, zero-count removal re-arms."""
    prim, agg = PrimaryIndex(), AggregateIndex()
    ing = EventIngestor(IngestConfig(pad_to=64), event_pcfg(), prim, agg,
                        names={0: "fs"})
    # "scan": three uid-3 files loaded by path, summary published by the
    # snapshot pipeline (out-of-band of this ingestor)
    prim.upsert_batch(["/fs/a", "/fs/b", "/fs/c"],
                      {"size": np.array([1.0, 2.0, 3.0], np.float32),
                       "uid": np.array([3, 3, 3], np.int32),
                       "gid": np.array([1, 1, 1], np.int32)},
                      np.array([1, 1, 1]))
    snap_stats = {"total": 6.0, "p50": 2.0}
    agg.put("user:3", {"file_count": 3.0, "size": dict(snap_stats)})
    ing.register_tree(parents={10: 0, 11: 0, 12: 0},
                      names={10: "a", 11: "b", 12: "c"})
    assert not ing.counts_exact
    s = ev.EventStream(start_fid=100)
    s.emit(ev.E_UNLNK, 10, 0)            # delete ONE of the three
    ing.ingest(s.take())
    assert len(prim) == 2
    assert agg.get("user:3") is not None, \
        "unseeded delta counts deleted a snapshot-built summary"
    # aggregate half of the handoff: seed the true counting matrix
    # (post-delete truth: two live uid-3/gid-1 files)
    true_counts = np.zeros_like(ing.counts)
    true_counts[3, 0] = 2.0
    true_counts[ing.pcfg.n_users + 1, 0] = 2.0
    ing.seed_counts(true_counts)
    assert ing.counts_exact
    s.emit(ev.E_UNLNK, 11, 0)            # two -> one live file
    ing.ingest(s.take())
    # exact count > 0 but the ingestor's sketch never observed these
    # records: the snapshot-built stats must survive, only file_count
    # refreshes — no inf/nan garbage from an empty sketch row
    rec = agg.get("user:3")
    assert rec is not None and rec["file_count"] == 1.0
    assert rec["size"] == snap_stats
    s.emit(ev.E_UNLNK, 12, 0)            # delete the last one
    ing.ingest(s.take())
    assert len(prim) == 0
    assert agg.get("user:3") is None     # now authoritative: ghost drops


def test_full_republication_drops_zero_count_principals():
    """from_sketch_state with only=None (full publication) is
    authoritative and removes stale records even without exact counts;
    a partial refresh without counts leaves them (bounded staleness)."""
    from repro.core.sketches import ddsketch as dds
    pcfg = event_pcfg()
    agg = AggregateIndex()
    agg.put("user:0", {"file_count": 9.0, "size": {"total": 1.0}})
    names = [f"p{i}" for i in range(pcfg.n_principals)]
    names[0] = "user:0"
    state = dds.init(pcfg.sketch, (pcfg.n_principals, len(snap.ATTRS)))
    state = {k: np.asarray(v) for k, v in state.items()}
    # partial refresh, no counts: user:0 survives (not authoritative)
    agg.from_sketch_state(pcfg.sketch, state, names, only=[0])
    assert agg.get("user:0") is not None
    # full republication from the (empty) state: user:0 is dropped
    agg.from_sketch_state(pcfg.sketch, state, names)
    assert agg.get("user:0") is None


def test_compact_with_aggregates_disabled_leaves_aggregate_alone():
    """Regression: compact_if_needed with an update_aggregates=False
    ingestor must not republish (its counts matrix is all zeros by
    construction and would wipe externally-built records)."""
    prim, agg = PrimaryIndex(), AggregateIndex()
    ing = EventIngestor(
        IngestConfig(pad_to=64, update_aggregates=False), event_pcfg(),
        prim, agg, names={0: "fs"})
    s = ev.EventStream(start_fid=1)
    for i in range(3):
        f = s.alloc_fid()
        s.emit(ev.E_CREAT, f, 0, has_stat=1, size=1.0, uid=3, gid=1,
               name=f"f{f}")
    ing.ingest(s.take(), names=s.names)
    agg.put("user:3", {"file_count": 3.0, "size": {"total": 3.0}})
    prim.delete_batch(list(prim.live_paths()), np.array([100]))
    assert compact_if_needed(prim, threshold=0.1, ingestor=ing) == 3
    assert agg.get("user:3") is not None    # untouched


def test_compaction_republishes_dead_principals_out():
    """compact_if_needed with an ingestor flushes ghosts: republication
    of the principals the dead rows touched uses exact counts, so a
    stale record for an all-dead principal is removed even if the
    normal event path never got to republish it."""
    prim, agg = PrimaryIndex(), AggregateIndex()
    ing = EventIngestor(IngestConfig(pad_to=64), event_pcfg(), prim, agg,
                        names={0: "fs"})
    s = ev.EventStream(start_fid=1)
    for i in range(4):
        f = s.alloc_fid()
        s.emit(ev.E_CREAT, f, 0, has_stat=1, size=10.0, uid=6, gid=2,
               name=f"f{f}")
    ing.ingest(s.take(), names=s.names)
    # tombstone behind the aggregate's back (direct index mutation)
    prim.delete_batch(list(prim.live_paths()), np.array([1000]))
    agg.put("user:6", dict(agg.get("user:6")))   # stale survivor
    assert prim.slot_stats()["dead_fraction"] == 1.0
    ing.counts[:] = 0.0                           # truth: nothing live
    reclaimed = compact_if_needed(prim, threshold=0.5, ingestor=ing)
    assert reclaimed == 4
    assert agg.get("user:6") is None


# ---------------------------------------------------------------------------
# compaction: observable-state preservation across slot maps and layouts
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("slot_map_factory",
                         [DictSlotMap, None])   # None -> HashSlotMap
def test_compact_preserves_state_both_slot_maps(slot_map_factory):
    if slot_map_factory is None:
        pytest.importorskip("pandas")
        from repro.core.sharded_index import HashSlotMap
        slot_map_factory = HashSlotMap
    files = files_only(synth_filesystem(1500, n_dirs=60, seed=3))
    idx = PrimaryIndex(slot_map=slot_map_factory())
    idx.ingest_table(files, 1)
    rng = np.random.default_rng(3)
    doomed = rng.choice(files.paths, size=900, replace=False)
    idx.delete_batch(list(doomed), np.array([2]))
    before = sorted_live(idx)
    sample = [p for p in files.paths if p not in set(doomed)][:20]
    vers_before = [idx.lookup(p)["version"] for p in sample]

    assert idx.slot_stats()["dead_fraction"] > 0.5
    reclaimed = idx.compact(slot_map_factory=slot_map_factory)
    assert reclaimed == 900
    assert idx.slot_stats() == {
        "slots": len(files) - 900, "live": len(files) - 900,
        "dead": 0, "dead_fraction": 0.0}
    assert type(idx.slot_map) is slot_map_factory
    after = sorted_live(idx)
    for k in before:
        assert np.array_equal(before[k], after[k]), k
    # versions survive (the idempotent-replay clock is untouched) ...
    assert [idx.lookup(p)["version"] for p in sample] == vers_before
    # ... so stale mutations still lose after compaction
    idx.delete_batch(sample[:1], np.array([0]))
    assert idx.lookup(sample[0]) is not None
    # and the index stays fully writable: re-ingest + new deletes work
    idx.ingest_table(files, 3)
    assert len(idx) == len(files)


def table1_answers(q):
    """The Table-I suite's answers, order-free (compaction renumbers
    slots, and scan order follows slots)."""
    return [sorted(q.find_by_name(r"f1\d\d$")),
            sorted(q.not_accessed_since(90 * 86400)),
            sorted(q.large_cold_files(1e5, 180 * 86400)),
            sorted(q.world_writable()),
            sorted(q.past_retention(2 * 365 * 86400)),
            {k: sorted(v) for k, v in q.duplicate_candidates().items()}]


@pytest.mark.parametrize("n_shards", [None, 4])
def test_compaction_leaves_table1_answers_unchanged(n_shards):
    """An engine that has already served the Table-I suite (its arenas
    cached) answers every query the same after compacting a 70%
    tombstoned index."""
    files = files_only(synth_filesystem(3000, n_dirs=100, seed=4))
    idx = make_primary(n_shards)
    idx.ingest_table(files, 1)
    rng = np.random.default_rng(4)
    doomed = rng.choice(files.paths, size=int(0.7 * len(files)),
                        replace=False)
    idx.delete_batch(list(doomed), np.full(len(doomed), 2, np.int64))
    q = QueryEngine(idx, AggregateIndex(), now=1.7e9)
    before = table1_answers(q)
    assert all(len(ans) for ans in before[:5])   # the scans match rows
    assert compact_if_needed(idx, threshold=0.3) == len(doomed)
    assert table1_answers(q) == before


def test_sharded_compact_per_shard_threshold():
    """Each shard compacts independently: deleting one shard's records
    rewrites only that shard (others keep their slot count)."""
    files = files_only(synth_filesystem(2000, n_dirs=80, seed=7))
    shd = ShardedPrimaryIndex(4)
    shd.ingest_table(files, 1)
    victim = 2
    doomed = [p for p in files.paths if shd.shard_of(p) == victim]
    shd.delete_batch(doomed, np.array([2]))
    slots_before = [len(sh.slot_map) for sh in shd.shards]
    reclaimed = shd.compact(threshold=0.5)
    assert reclaimed == len(doomed)
    for si, sh in enumerate(shd.shards):
        if si == victim:
            assert len(sh.slot_map) == 0
        else:
            assert len(sh.slot_map) == slots_before[si]
    # global stats reflect the rewrite
    assert shd.slot_stats()["dead"] == 0


def test_compact_below_threshold_is_noop():
    files = files_only(synth_filesystem(500, n_dirs=40, seed=2))
    idx = PrimaryIndex()
    idx.ingest_table(files, 1)
    idx.delete_batch(list(files.paths[:10]), np.array([2]))
    assert compact_if_needed(idx, threshold=0.5) == 0
    assert idx.slot_stats()["dead"] == 10


def test_reconcile_then_compact_chained():
    """compact_threshold chains compaction onto the reconcile pass: the
    tombstones the repair deletes just created are reclaimed in the
    same call when they cross the threshold."""
    files = files_only(synth_filesystem(800, n_dirs=50, seed=9))
    idx = PrimaryIndex()
    idx.ingest_table(files, 1)
    truth = files.select(np.arange(len(files)) < 300)   # 500 deleted
    rep = reconcile(truth, version=2, primary=idx,
                    compact_threshold=0.3)
    assert rep.applied_tombstones == len(files) - 300
    assert rep.reclaimed_slots == len(files) - 300
    assert idx.slot_stats() == {"slots": 300, "live": 300, "dead": 0,
                                "dead_fraction": 0.0}
    rebuilt = PrimaryIndex()
    rebuilt.ingest_table(truth, 1)
    assert_same_live(idx, rebuilt)


def test_report_repairs_property():
    rep = ReconcileReport(creates=2, updates=3, deletes=4)
    assert rep.repairs == 9


@pytest.mark.parametrize("n_shards", [None, 3])
def test_compaction_floor_blocks_stale_resurrection(n_shards):
    """Regression: compacting a tombstone away must not re-open the
    door the version gate had closed — a pre-compaction scan's create
    repair (or a stale event replay) for the reclaimed subject must
    stay dead. Reclaimed tombstone versions fold into tombstone_floor
    and fresh slots materialize AT the floor."""
    idx = make_primary(n_shards)
    t = tiny_table(["/fs/p", "/fs/q"], [1.0, 2.0])
    idx.ingest_table(t, 90)                          # scan at seq 90
    idx.delete_batch(["/fs/p"], np.array([100]))     # feed deletes at 100
    assert compact_if_needed(idx, threshold=0.1) == 1

    rep = reconcile(t, version=90, primary=idx)      # STALE scan
    assert rep.creates == 1                          # diff flags it...
    assert idx.lookup("/fs/p") is None               # ...gate blocks it
    ph = np.array([path_hash("/fs/p")], np.uint32)
    idx.upsert_batch(["/fs/p"], {"path_hash": ph,    # stale replay too
                                 "size": np.array([9.0], np.float32)},
                     np.array([95]))
    assert idx.lookup("/fs/p") is None
    idx.upsert_batch(["/fs/p"], {"path_hash": ph,    # fresher write wins
                                 "size": np.array([9.0], np.float32)},
                     np.array([101]))
    assert idx.lookup("/fs/p")["size"] == 9.0


@pytest.mark.parametrize("n_shards", [None, 3])
def test_reconcile_after_compact_to_zero(n_shards):
    """Regression: a shard compacted down to ZERO slots still has its
    column keys (length-0 arenas); diffing a populated snapshot against
    it must take the create path, not crash on the empty gather."""
    files = files_only(synth_filesystem(200, n_dirs=20, seed=0))
    idx = make_primary(n_shards)
    idx.ingest_table(files, 1)
    rep = reconcile(files.select(np.zeros(len(files), bool)),
                    version=2, primary=idx)          # empty scan
    assert rep.deletes == len(files) and len(idx) == 0
    assert compact_if_needed(idx, threshold=0.1) == len(files)
    assert idx.slot_stats()["slots"] == 0
    rep = reconcile(files, version=3, primary=idx)   # repopulate
    assert rep.creates == len(files)
    rebuilt = make_primary(n_shards)
    rebuilt.ingest_table(files, 1)
    assert_same_live(idx, rebuilt, f"shards={n_shards}")
