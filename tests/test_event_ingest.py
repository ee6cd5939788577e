"""Event-based ingestion into the dual index (event_ingest.py).

Core contract: a snapshot followed by a replayed event suffix must leave
the primary index equal to a snapshot of the final state — including
renames, deletes, and replaying the same events twice (idempotency by the
shared snapshot/changelog version clock).
"""
import functools

import numpy as np
import pytest

from repro.core import events as ev
from repro.core import snapshot as snap
from repro.core.event_ingest import EventIngestor, IngestConfig
from repro.core.index import AggregateIndex, PrimaryIndex
from repro.core.metadata import crc32_shard, path_hash
from repro.core.monitor import Monitor, MonitorConfig
from repro.core.query import QueryEngine
from repro.core.sketches.ddsketch import DDSketchConfig
from repro.kernels.segstats import ops as seg_ops

PCFG = snap.PipelineConfig(
    n_users=8, n_groups=4, n_dirs=20,
    sketch=DDSketchConfig(alpha=0.05, n_buckets=512, offset=32))


def make_ingestor(mode="eager", **kw):
    prim, agg = PrimaryIndex(), AggregateIndex()
    ing = EventIngestor(IngestConfig(mode=mode, pad_to=64, **kw), PCFG,
                        prim, agg, names={0: "fs"})
    return ing, prim, agg


def replay_reference(batches, names):
    """Per-event python replay -> final path -> stat map (files only)."""
    parent, name, stat, isdir = {0: -1}, dict(names), {}, {0: True}

    def path(f):
        parts = []
        while f >= 0:
            parts.append(name.get(f, f"#{f}"))
            f = parent.get(f, -1)
        return "/" + "/".join(reversed(parts))

    for b in batches:
        for i in np.argsort(b["seq"]):
            et, fid = int(b["etype"][i]), int(b["fid"][i])
            pf, npf = int(b["parent_fid"][i]), int(b["new_parent_fid"][i])
            if et in (ev.E_CREAT, ev.E_MKDIR):
                parent[fid] = pf
                isdir[fid] = et == ev.E_MKDIR
                if et == ev.E_CREAT:
                    stat[fid] = {"size": float(b["size"][i]),
                                 "mtime": float(b["mtime"][i]),
                                 "uid": int(b["uid"][i]),
                                 "gid": int(b["gid"][i])}
            elif et in (ev.E_UNLNK, ev.E_RMDIR):
                stat.pop(fid, None)
                isdir.pop(fid, None)
            elif et == ev.E_RENME:
                if npf >= 0:
                    parent[fid] = npf
            elif et in (ev.E_SATTR, ev.E_CLOSE, ev.E_WRITE):
                if b["has_stat"][i] and fid in stat:
                    stat[fid].update(size=float(b["size"][i]),
                                     mtime=float(b["mtime"][i]))
    return {path(f): s for f, s in stat.items() if not isdir.get(f)}


def scripted_stream():
    """Creates, updates, a dir rename, and deletes — every rule family."""
    s = ev.EventStream(start_fid=1)
    d1 = s.alloc_fid()
    s.emit(ev.E_MKDIR, d1, 0, is_dir=1, name=f"d{d1}")
    d2 = s.alloc_fid()
    s.emit(ev.E_MKDIR, d2, d1, is_dir=1, name=f"d{d2}")   # /fs/d1/d2
    files = []
    for i in range(12):
        f = s.alloc_fid()
        par = [0, d1, d2][i % 3]
        s.emit(ev.E_CREAT, f, par, has_stat=1, size=100.0 * (i + 1),
               mtime=10.0 + i, uid=i % 5, gid=i % 3, name=f"f{f}")
        files.append(f)
    # updates
    s.emit(ev.E_SATTR, files[0], 0, has_stat=1, size=7777.0, mtime=99.0)
    s.emit(ev.E_WRITE, files[1], d1, has_stat=1, size=1.5, mtime=98.0)
    # delete (tombstone) + created-then-deleted (cancelled)
    s.emit(ev.E_UNLNK, files[2], d2)
    tmp = s.alloc_fid()
    s.emit(ev.E_CREAT, tmp, d1, has_stat=1, size=5.0, name=f"f{tmp}")
    s.emit(ev.E_UNLNK, tmp, d1)
    # directory rename: mv /fs/d1/d2 /fs/d2  (reparent to root)
    s.emit(ev.E_RENME, d2, d1, 0, is_dir=1)
    return s, d1, d2, files


def drain(stream, ing, bs=None):
    batches = []
    while len(stream):
        b = stream.take(bs)
        batches.append({k: v.copy() for k, v in b.items()})
        ing.ingest(b, names=stream.names)
    return batches


# ---------------------------------------------------------------------------
# primary index: events == snapshot of final state
# ---------------------------------------------------------------------------

def assert_matches_reference(prim, want):
    live = prim.live()
    got = {p: i for i, p in enumerate(live["path"])}
    assert set(got) == set(want)
    for p, st in want.items():
        i = got[p]
        assert live["size"][i] == pytest.approx(st["size"]), p
        assert live["mtime"][i] == pytest.approx(st["mtime"]), p
        assert live["uid"][i] == st["uid"], p
        assert live["gid"][i] == st["gid"], p
        assert live["path_hash"][i] == path_hash(p), p


@pytest.mark.parametrize("bs", [None, 7])
def test_events_match_final_state(bs):
    """Rename, delete-tombstone, update: event path == final-state replay
    (bs=7 also exercises cross-batch coalescing)."""
    s, d1, d2, files = scripted_stream()
    ing, prim, agg = make_ingestor()
    batches = drain(s, ing, bs)
    want = replay_reference(batches, {0: "fs", **s.names})
    assert len(want) == 11                        # 12 created, 1 deleted
    assert f"/fs/d{d2}/f{files[5]}" in want       # repathed by the rename
    assert_matches_reference(prim, want)
    assert ing.metrics["cancelled"] >= 1          # tmp create+delete


def test_idempotent_replay():
    """Replaying the same event batches leaves the index unchanged
    (versions are changelog seqs; >= gate makes replay a no-op)."""
    s, *_ = scripted_stream()
    ing, prim, agg = make_ingestor()
    batches = drain(s, ing)
    live1 = {p: v for p, v in zip(prim.live()["path"],
                                  prim.live()["size"])}
    counts1 = ing.counts.copy()
    for b in batches:                             # replay the whole suffix
        ing.ingest(b)
    live2 = {p: v for p, v in zip(prim.live()["path"],
                                  prim.live()["size"])}
    assert live1 == live2
    np.testing.assert_allclose(ing.counts, counts1)   # no double counting


def test_snapshot_then_events_versions():
    """Snapshot ingest and event ingest share one version clock: a
    snapshot re-ingest at a later changelog seq supersedes event records,
    and stale events replayed after it are dropped."""
    from repro.core.metadata import synth_filesystem
    fs = synth_filesystem(500, n_users=8, n_groups=4, n_dirs=30, seed=7)
    ing, prim, agg = make_ingestor()
    prim.ingest_table(fs, version=1)
    n0 = len(prim)
    s = ev.EventStream(start_fid=1)
    f = s.alloc_fid()
    s.emit(ev.E_CREAT, f, 0, has_stat=1, size=42.0, mtime=1.0, name=f"f{f}")
    batch = s.take()
    ing.ingest(batch, names=s.names)
    assert len(prim) == n0 + 1
    # snapshot re-ingest at a later seq kills the event-derived record
    prim.ingest_table(fs, version=1000)
    assert len(prim) == n0
    # stale event replay after the snapshot: dropped by the version gate
    ing.ingest(batch)
    assert len(prim) == n0


# ---------------------------------------------------------------------------
# aggregate index: counts match an independent segstats-style reference
# ---------------------------------------------------------------------------

def reference_counts(prim):
    """Independent (P, S) count matrix from the live primary view, using
    the paper's slot rules (uid/gid modulo, dir-prefix hash, crc32)."""
    counts = np.zeros((PCFG.n_principals, PCFG.n_shards), np.float32)
    live = prim.live()
    base = PCFG.n_users + PCFG.n_groups
    for p, uid, gid in zip(live["path"], live["uid"], live["gid"]):
        sid = crc32_shard(p.encode(), PCFG.n_shards)
        counts[int(uid) % PCFG.n_users, sid] += 1
        counts[PCFG.n_users + int(gid) % PCFG.n_groups, sid] += 1
        comps = [c for c in p.split("/") if c][:-1]     # parent dir comps
        for depth in range(PCFG.dir_min, PCFG.dir_max + 1):
            if depth < len(comps):
                anc = "/" + "/".join(comps[:depth + 1])
                counts[base + path_hash(anc) % PCFG.n_dirs, sid] += 1
    return counts


@pytest.mark.parametrize("use_kernel", [False, True])
def test_aggregate_counts_match_segstats_reference(use_kernel, monkeypatch):
    """After an event batch (incl. deletes + a rename), the maintained
    (P, S) counts equal a from-scratch reference over the live index —
    with both the CPU form of the segstats entry point (its jnp oracle)
    and the Pallas segstats kernel (interpret mode) swapped in."""
    if use_kernel:
        monkeypatch.setattr(seg_ops, "segstats", functools.partial(
            seg_ops.segstats_kernel, interpret=True))
    s, *_ = scripted_stream()
    ing, prim, agg = make_ingestor()
    drain(s, ing)
    np.testing.assert_allclose(ing.counts, reference_counts(prim))


def test_aggregate_summaries_published():
    """Touched principals get Table-III records with correct totals for
    first-seen observations."""
    s = ev.EventStream(start_fid=1)
    for i in range(6):
        f = s.alloc_fid()
        s.emit(ev.E_CREAT, f, 0, has_stat=1, size=1000.0, mtime=5.0,
               uid=3, gid=1, name=f"f{f}")
    ing, prim, agg = make_ingestor()
    drain(s, ing)
    rec = agg.get("user:3")
    assert rec is not None
    assert rec["file_count"] == 6
    assert rec["size"]["total"] == pytest.approx(6000.0)


def test_truncate_then_statfree_event_batch_invariant():
    """A stat-carrying zero-size update (truncate) must win over an older
    nonzero size even when the fid's LAST event in the batch is stat-free
    — coalescing cannot depend on micro-batch boundaries."""
    results = []
    for bs in (None, 1):
        ing, prim, agg = make_ingestor()
        s = ev.EventStream(start_fid=1)
        f = s.alloc_fid()
        s.emit(ev.E_CREAT, f, 0, has_stat=1, size=100.0, name="t")
        s.emit(ev.E_SATTR, f, 0, has_stat=1, size=0.0)   # truncate
        s.emit(ev.E_CLOSE, f, 0)                          # stat-free tail
        drain(s, ing, bs)
        results.append(float(prim.live()["size"][0]))
    assert results == [0.0, 0.0]


def test_recreate_after_delete_counts_again():
    """A subject deleted then recreated (new fid, same path) must re-enter
    the counting matrix: upsert_batch's +1 mask covers resurrected
    tombstones, not just brand-new slots."""
    ing, prim, agg = make_ingestor()
    s = ev.EventStream(start_fid=1)
    f = s.alloc_fid()
    s.emit(ev.E_CREAT, f, 0, has_stat=1, size=1.0, uid=2, gid=1, name="x")
    ing.ingest(s.take(), names=s.names)
    s.emit(ev.E_UNLNK, f, 0)
    ing.ingest(s.take())
    g = s.alloc_fid()
    s.emit(ev.E_CREAT, g, 0, has_stat=1, size=2.0, uid=2, gid=1, name="x")
    ing.ingest(s.take(), names=s.names)
    assert len(prim) == 1
    np.testing.assert_allclose(ing.counts, reference_counts(prim))


def test_chown_moves_counts_between_principals():
    """An ownership change on a live record must MOVE its count to the
    new principal — enter/leave deltas alone strand it on the old owner
    (and would let exact-count republication ghost-drop a principal
    that still owns files)."""
    ing, prim, agg = make_ingestor()
    s = ev.EventStream(start_fid=1)
    f = s.alloc_fid()
    s.emit(ev.E_CREAT, f, 0, has_stat=1, size=10.0, uid=1, gid=1,
           name=f"f{f}")
    ing.ingest(s.take(), names=s.names)
    s.emit(ev.E_SATTR, f, 0, has_stat=1, size=10.0, uid=2, gid=2)
    ing.ingest(s.take())
    live = prim.live()
    assert int(live["uid"][0]) == 2
    np.testing.assert_allclose(ing.counts, reference_counts(prim))
    s.emit(ev.E_UNLNK, f, 0)             # -1 lands on the NEW owner
    ing.ingest(s.take())
    np.testing.assert_allclose(ing.counts, np.zeros_like(ing.counts))


def test_file_rename_moves_subject():
    """A FILE rename (not just a dir rename) must tombstone the old
    subject and index the new one — no duplicate live records, counts
    conserved."""
    ing, prim, agg = make_ingestor()
    s = ev.EventStream(start_fid=1)
    d1, d2 = s.alloc_fid(), s.alloc_fid()
    s.emit(ev.E_MKDIR, d1, 0, is_dir=1, name=f"d{d1}")
    s.emit(ev.E_MKDIR, d2, 0, is_dir=1, name=f"d{d2}")
    f = s.alloc_fid()
    s.emit(ev.E_CREAT, f, d1, has_stat=1, size=7.0, uid=3, gid=1,
           name=f"f{f}")
    ing.ingest(s.take(), names=s.names)
    s.emit(ev.E_RENME, f, d1, d2)            # mv d1/f -> d2/f, later batch
    ing.ingest(s.take())
    live = sorted(prim.live()["path"])
    assert live == [f"/fs/d{d2}/f{f}"]        # old subject tombstoned
    np.testing.assert_allclose(ing.counts, reference_counts(prim))


def test_register_tree_snapshot_handoff():
    """Events on fids the scanner saw (register_tree bootstrap) resolve to
    the snapshot-loaded subjects; the counting delta is attributed to the
    record's real owner; unknown fids are counted loudly."""
    ing, prim, agg = make_ingestor()
    # "scan": two files under /fs, loaded by path
    prim.upsert_batch(["/fs/a", "/fs/b"],
                      {"size": np.array([1.0, 2.0], np.float32),
                       "uid": np.array([1, 2], np.int32),
                       "gid": np.array([1, 2], np.int32)},
                      np.array([1, 1]))
    ing.register_tree(parents={10: 0, 11: 0}, names={10: "a", 11: "b"})
    s = ev.EventStream(start_fid=100)
    s.emit(ev.E_UNLNK, 10, 0)                # delete pre-scan file by fid
    ing.ingest(s.take())
    assert sorted(prim.live()["path"]) == ["/fs/b"]
    assert ing.metrics["unresolved"] == 0
    # the -1 delta lands on the record's owner (user:1), not user:0
    assert ing.counts[1].sum() == -1.0
    assert ing.counts[0].sum() == 0.0
    s.emit(ev.E_UNLNK, 999, 0)               # fid nobody registered
    ing.ingest(s.take())
    assert ing.metrics["unresolved"] > 0     # loud, and /fs/b untouched
    assert sorted(prim.live()["path"]) == ["/fs/b"]


def test_register_tree_dir_rename_repaths_scanned_files():
    """A dir rename must re-path descendants the ingestor knows only via
    register_tree (no event-derived stat): the new subject inherits the
    indexed record's fields."""
    ing, prim, agg = make_ingestor()
    prim.upsert_batch(["/fs/proj/data.bin"],
                      {"size": np.array([42.0], np.float32),
                       "uid": np.array([3], np.int32),
                       "gid": np.array([1], np.int32)},
                      np.array([1]))
    ing.register_tree(parents={5: 0, 7: 5}, names={5: "proj", 7: "data.bin"},
                      is_dir={5: True})
    s = ev.EventStream(start_fid=100)
    d2 = s.alloc_fid()
    s.emit(ev.E_MKDIR, d2, 0, is_dir=1, name="archive")
    s.emit(ev.E_RENME, 5, 0, d2, is_dir=1)   # mv /fs/proj /fs/archive/proj
    ing.ingest(s.take(), names=s.take_names())
    live = prim.live()
    assert sorted(live["path"]) == ["/fs/archive/proj/data.bin"]
    i = list(live["path"]).index("/fs/archive/proj/data.bin")
    assert live["size"][i] == 42.0 and live["uid"][i] == 3


def test_dir_rename_without_flag_in_later_batch():
    """A RENME on a known directory whose event omits is_dir must still
    trigger the rename override (state-manager memory wins) and must NOT
    index the directory as a file."""
    ing, prim, agg = make_ingestor()
    s = ev.EventStream(start_fid=1)
    d = s.alloc_fid()
    s.emit(ev.E_MKDIR, d, 0, is_dir=1, name=f"d{d}")
    f = s.alloc_fid()
    s.emit(ev.E_CREAT, f, d, has_stat=1, size=3.0, name=f"f{f}")
    ing.ingest(s.take(), names=s.names)
    d2 = s.alloc_fid()
    s.emit(ev.E_MKDIR, d2, 0, is_dir=1, name=f"d{d2}")
    s.emit(ev.E_RENME, d, 0, d2)            # note: is_dir omitted
    ing.ingest(s.take(), names=s.names)
    live = sorted(prim.live()["path"])
    assert live == [f"/fs/d{d2}/d{d}/f{f}"]   # repathed, dir not indexed


# ---------------------------------------------------------------------------
# buffered mode: freshness window + watermark through QueryEngine
# ---------------------------------------------------------------------------

def test_buffered_watermark_through_query_engine():
    t = {"now": 0.0}
    prim, agg = PrimaryIndex(), AggregateIndex()
    ing = EventIngestor(
        IngestConfig(mode="buffered", freshness_window=5.0,
                     max_buffer_events=1000, pad_to=64),
        PCFG, prim, agg, names={0: "fs"}, clock=lambda: t["now"])
    q = QueryEngine(prim, agg, ingestor=ing)

    s = ev.EventStream(start_fid=1)
    for i in range(4):
        f = s.alloc_fid()
        s.emit(ev.E_CREAT, f, 0, has_stat=1, size=10.0, name=f"f{f}")
    ing.ingest(s.take(), names=s.names)

    # inside the freshness window: nothing visible, watermark says so
    fr = q.freshness()
    assert fr["pending_events"] == 4 and fr["applied_seq"] == 0
    assert len(prim) == 0
    out = q.query("find_by_name", "f")
    assert len(out["result"]) == 0
    assert out["freshness"]["pending_events"] == 4

    # window expires -> tick applies, watermark advances
    t["now"] = 6.0
    assert ing.tick() == 4
    fr = q.freshness()
    assert fr["pending_events"] == 0 and fr["applied_seq"] == 4
    assert len(q.query("find_by_name", "f")["result"]) == 4

    # size trigger: buffer past max_buffer_events applies immediately
    for i in range(5):
        f = s.alloc_fid()
        s.emit(ev.E_CREAT, f, 0, has_stat=1, size=1.0, name=f"f{f}")
    ing2_cfg = IngestConfig(mode="buffered", freshness_window=1e9,
                            max_buffer_events=5, pad_to=64)
    ing2 = EventIngestor(ing2_cfg, PCFG, prim, agg, names={0: "fs"},
                         clock=lambda: t["now"])
    ing2.ingest(s.take(), names=s.names)
    assert ing2.freshness()["pending_events"] == 0
    assert len(prim) == 9


def test_eager_mode_immediately_visible():
    ing, prim, agg = make_ingestor(mode="eager")
    s = ev.EventStream(start_fid=1)
    f = s.alloc_fid()
    s.emit(ev.E_CREAT, f, 0, has_stat=1, size=10.0, name=f"f{f}")
    ing.ingest(s.take(), names=s.names)
    assert len(prim) == 1
    assert ing.freshness()["pending_events"] == 0


# ---------------------------------------------------------------------------
# monitor threading: one consumer feeds hierarchy AND dual index
# ---------------------------------------------------------------------------

def test_monitor_feeds_dual_index():
    s = ev.EventStream(start_fid=1)
    ev.filebench_workload(s, 60, 30, seed=3, has_stat=1,
                          n_users=PCFG.n_users, n_groups=PCFG.n_groups)
    ing, prim, agg = make_ingestor()
    mon = Monitor(MonitorConfig(max_fids=4096, batch_size=256),
                  ingestor=ing)
    r = mon.run(s)
    assert r["watermark_seq"] == ing.freshness()["applied_seq"] > 0
    assert r["pending_events"] == 0
    assert len(prim) == 60                     # all created files indexed
    np.testing.assert_allclose(ing.counts, reference_counts(prim))
