"""Snapshot pipelines, dual index, query engine, event log, batcher."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import snapshot as snap
from repro.core.eventlog import EventLog
from repro.core.index import AggregateIndex, PrimaryIndex
from repro.core.metadata import files_only, path_hash, synth_filesystem
from repro.core.query import QueryEngine
from repro.core.records import IngestBatcher
from repro.core.sketches.ddsketch import DDSketchConfig

PCFG = snap.PipelineConfig(
    n_users=16, n_groups=8, n_dirs=40,
    # 512 buckets need coarser alpha to span file-size ranges (see covers())
    sketch=DDSketchConfig(alpha=0.05, n_buckets=512, offset=32))


@pytest.fixture(scope="module")
def fs():
    return synth_filesystem(4000, n_users=16, n_groups=8, seed=11)


@pytest.fixture(scope="module")
def rows(fs):
    rows_np, valid = snap.pad_rows(snap.preprocess(fs, PCFG), 256)
    return ({k: jnp.asarray(v) for k, v in rows_np.items()},
            jnp.asarray(valid))


def test_counting_matches_numpy(fs, rows):
    r, valid = rows
    counts = np.asarray(snap.counting_local(PCFG, r, valid))
    files = files_only(fs)
    # user counts: row sums over shards must equal per-user file counts
    for u in range(16):
        want = int(((files.uid % 16) == u).sum())
        got = counts[u].sum()
        assert got == want, (u, got, want)


def test_counting_shard_assignment_crc32(fs, rows):
    """Shard ids follow the paper's zlib.crc32 % 64 rule."""
    import zlib
    files = files_only(fs)
    r, _ = rows
    sid = np.asarray(r["shard_id"])[:len(files)]
    for i in range(0, len(files), 997):
        assert sid[i] == zlib.crc32(files.paths[i].encode()) % 64


def test_aggregate_quantiles_near_exact(fs, rows):
    r, valid = rows
    state = snap.aggregate_local(PCFG, r, valid)
    files = files_only(fs)
    from repro.core.sketches import ddsketch as dds
    for u in (1, 2):
        vals = files.size[(files.uid % 16) == u]
        if len(vals) < 50:
            continue
        sub = jax.tree.map(lambda s: s[u, 0], state)  # attr 0 = size
        for q in (0.25, 0.5, 0.9):
            est = float(dds.quantile(PCFG.sketch, sub, q))
            exact = float(np.quantile(vals, q, method="lower"))
            assert abs(est - exact) / exact < 3 * PCFG.sketch.alpha, (u, q, est, exact)


def test_recursive_dir_counts():
    #      0
    #     / \
    #    1   2
    #    |
    #    3
    parent = np.array([-1, 0, 0, 1])
    depth = np.array([0, 1, 1, 2])
    nonrec = np.array([1.0, 2.0, 3.0, 4.0])
    rec = snap.recursive_dir_counts(nonrec, parent, depth)
    np.testing.assert_array_equal(rec, [10.0, 6.0, 3.0, 4.0])


def test_primary_index_version_idempotency(fs):
    idx = PrimaryIndex()
    idx.ingest_table(fs, version=1)
    n1 = len(idx)
    # re-ingest same snapshot with same version: no change
    idx.ingest_table(fs, version=1)
    assert len(idx) == n1
    # new snapshot without half the files -> stale records invalidated
    files = files_only(fs)
    keep = fs.select(np.arange(len(fs)) % 2 == 0)
    idx.ingest_table(keep, version=2)
    assert len(idx) < n1
    # stale (version 1) records are dead
    live = idx.live()
    assert all(v == 2 for v in idx.version[:len(idx._slot)][
        idx.alive[:len(idx._slot)]])


def test_primary_index_updates_and_deletes():
    idx = PrimaryIndex()
    idx.upsert("/fs/a", {"uid": np.int32(1), "size": np.float32(10)}, 1)
    idx.upsert("/fs/a", {"uid": np.int32(1), "size": np.float32(99)}, 2)
    assert idx.live()["size"][0] == 99
    # stale delete (older version) ignored
    idx.delete("/fs/a", 1)
    assert len(idx) == 1
    idx.delete("/fs/a", 3)
    assert len(idx) == 0


def test_query_engine_suite(fs):
    idx = PrimaryIndex()
    idx.ingest_table(fs, version=1)
    rows_np, valid = snap.pad_rows(snap.preprocess(fs, PCFG), 256)
    state = snap.aggregate_local(
        PCFG, {k: jnp.asarray(v) for k, v in rows_np.items()},
        jnp.asarray(valid))
    agg = AggregateIndex()
    names = ([f"user:{i}" for i in range(16)]
             + [f"group:{i}" for i in range(8)]
             + [f"dir:{i}" for i in range(40)])
    agg.from_sketch_state(PCFG.sketch, state, names)
    q = QueryEngine(idx, agg)
    timings = q.run_table1_suite()
    assert len(timings) == 13
    assert all(t < 2.0 for t in timings.values())
    # cross-check per-user totals vs exact
    files = files_only(fs)
    usage = q.per_user_usage()
    for u in range(4):
        exact = float(files.size[(files.uid % 16) == u].sum())
        if f"user:{u}" in usage and exact > 0:
            got = usage[f"user:{u}"][0]
            assert abs(got - exact) / exact < 1e-3


def test_preprocess_reduces_row_volume(fs):
    """Preprocessing keeps numeric columns and principal slots only: at
    least 40% smaller than the paper's 22-column raw scan rows at 24
    bytes per field (paper §V-A reports 40-90%)."""
    rows_np, _ = snap.pad_rows(snap.preprocess(fs, PCFG), 256)
    pre_bytes = sum(v.nbytes for v in rows_np.values())
    assert pre_bytes <= 0.6 * len(fs) * 22 * 24


def test_aggregate_totals_exact_for_every_principal(fs, rows):
    """Every user's and group's file count in the aggregate index equals
    the exact count, and its byte total the exact sum to f32 rounding."""
    r, valid = rows
    agg = AggregateIndex()
    agg.from_sketch_state(
        PCFG.sketch, snap.aggregate_local(PCFG, r, valid),
        [f"user:{i}" for i in range(16)] + [f"group:{i}" for i in range(8)]
        + [f"dir:{i}" for i in range(40)])
    files = files_only(fs)
    for kind, col, n in (("user", files.uid, 16), ("group", files.gid, 8)):
        for p in range(n):
            mask = (col % n) == p
            rec = agg.get(f"{kind}:{p}")
            assert rec["file_count"] == mask.sum(), (kind, p)
            exact = float(files.size[mask].sum())
            assert abs(rec["size"]["total"] - exact) <= 1e-5 * exact, \
                (kind, p)


def test_eventlog_roundtrip(tmp_path):
    log = EventLog()
    t = log.topic("audit", n_partitions=2)
    for i in range(10):
        t.produce({"i": i}, key=i)
    got = log.consume("audit", "g1", 0, max_n=3)
    assert [r["i"] for r in got] == [0, 2, 4]
    assert log.lag("audit", "g1") == 7
    p = str(tmp_path / "log.zst")
    log.save(p)
    log2 = EventLog.load(p)
    got2 = log2.consume("audit", "g1", 0, max_n=10)
    assert [r["i"] for r in got2] == [6, 8]      # offsets persisted


def test_ingest_batcher_size_and_timeout():
    sent = []
    b = IngestBatcher(sink=lambda recs, rid: sent.append((rid, len(recs))),
                      max_bytes=2000, timeout_s=0.05)
    for i in range(100):
        b.add({"subject": f"/fs/file{i}", "content": {"size": i}})
    assert sent, "size-based flush"
    n_before = len(sent)
    b.add({"subject": "/fs/tail", "content": {}})
    time.sleep(0.08)
    b.tick()
    assert len(sent) == n_before + 1, "timeout flush"


def test_path_hash_stability():
    assert path_hash("/fs/a") != path_hash("/fs/b")
    assert path_hash("/fs/a") == path_hash("/fs/a")
