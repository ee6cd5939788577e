"""Durable pipeline unit + property tests (ISSUE 4 satellites).

- EventLog: round-robin keyless produce (hot-partition fix), clear
  ValueError on unknown topics / out-of-range partitions, explicit
  commit semantics (read-uncommitted, commit-after-apply, no backward
  commits), truncation/retention behind a barrier.
- PrimaryIndex / ShardedPrimaryIndex checkpoint/restore: byte-identical
  roundtrips (live view, versions, tombstone floor), layout-mismatch
  errors, torn-write atomicity.
- Property-based offset semantics: any interleaving of
  produce / pump / flush / crash never skips an offset, never commits
  one backwards, and full redelivery from offset zero is idempotent on
  the index (the exactly-once-effect claim, DESIGN.md §10.2).
- The log leg: a changelog through produce -> log -> consumer group
  leaves the index byte-identical to feeding the ingestor directly.
"""
import os

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import events as ev
from repro.core import snapshot as snap
from repro.core.event_ingest import EventIngestor, IngestConfig
from repro.core.eventlog import EventLog
from repro.core.index import AggregateIndex, PrimaryIndex
from repro.core.metadata import synth_filesystem
from repro.core.sharded_index import ShardedPrimaryIndex, index_from_state
from repro.core.stream_pipeline import DurablePipeline
from test_differential import assert_byte_identical

PCFG = snap.PipelineConfig(n_users=8, n_groups=4, n_dirs=16)


# ---------------------------------------------------------------------------
# EventLog: partitioning, negative paths, commit discipline, retention
# ---------------------------------------------------------------------------

def test_keyless_produce_round_robins():
    """produce(key=None) must spread across partitions, not pile onto
    partition 0 (the hot-partition skew bug)."""
    log = EventLog()
    t = log.topic("evts", n_partitions=4)
    for i in range(100):
        t.produce({"i": i})
    fills = [len(p) for p in t.partitions]
    assert fills == [25, 25, 25, 25], fills


def test_keyed_produce_still_routes_by_key():
    log = EventLog()
    t = log.topic("evts", n_partitions=3)
    for i in range(30):
        t.produce({"i": i}, key=7)       # sticky key -> one partition
    assert [len(p) for p in t.partitions] == [0, 30, 0]


def test_unknown_topic_raises_value_error():
    log = EventLog()
    log.topic("known", 2)
    for fn in (lambda: log.consume("nope", "g"),
               lambda: log.lag("nope", "g"),
               lambda: log.commit("nope", "g", 0, 0),
               lambda: log.truncate("nope"),
               lambda: log.committed("nope", "g")):
        with pytest.raises(ValueError, match="unknown topic"):
            fn()


def test_partition_out_of_range_raises_value_error():
    log = EventLog()
    log.topic("t", 2)
    with pytest.raises(ValueError, match="out of range"):
        log.consume("t", "g", partition=2)
    with pytest.raises(ValueError, match="out of range"):
        log.commit("t", "g", 5, 0)


def test_consume_uncommitted_and_explicit_commit():
    log = EventLog()
    t = log.topic("t", 1)
    for i in range(10):
        t.produce({"i": i}, key=0)
    # read without committing: a re-read sees the same records
    a = log.consume("t", "g", 0, max_n=4, commit=False)
    b = log.consume("t", "g", 0, max_n=4, commit=False)
    assert [r["i"] for r in a] == [r["i"] for r in b] == [0, 1, 2, 3]
    assert log.lag("t", "g") == 10
    log.commit("t", "g", 0, 4)
    assert log.committed("t", "g", 0) == 4
    assert log.lag("t", "g") == 6
    assert [r["i"] for r in log.consume("t", "g", 0, commit=False)][:2] \
        == [4, 5]
    # commits never move backwards (late duplicate ack after redelivery)
    log.commit("t", "g", 0, 2)
    assert log.committed("t", "g", 0) == 4
    # ... and never past the end
    with pytest.raises(ValueError, match="outside"):
        log.commit("t", "g", 0, 11)


def test_truncation_retires_prefix_and_guards_groups():
    log = EventLog()
    t = log.topic("t", 1)
    for i in range(10):
        t.produce({"i": i}, key=0)
    log.consume("t", "fast", 0, max_n=8)           # commits at 8
    log.consume("t", "slow", 0, max_n=3)           # commits at 3
    # barrier asks for 8, but "slow" has only acked 3: clamp
    dropped = log.truncate("t", {0: 8})
    assert dropped == 3 and t.partitions[0].base == 3
    # offsets stay absolute across truncation
    assert [r["i"] for r in log.consume("t", "slow", 0, max_n=2)] == [3, 4]
    # reading behind the barrier is loud, not silent
    with pytest.raises(ValueError, match="truncation barrier"):
        log.consume("t", "g2", 0, offset=0, commit=False)
    # a fresh group starts at the retention base
    assert log.committed("t", "g2", 0) == 3


def test_save_load_preserves_truncation_base():
    log = EventLog()
    t = log.topic("t", 2)
    for i in range(12):
        t.produce({"i": i})
    log.consume("t", "g", 0, max_n=6)
    log.consume("t", "g", 1, max_n=6)
    log.truncate("t")
    import tempfile
    p = os.path.join(tempfile.mkdtemp(), "log.zst")
    log.save(p)
    log2 = EventLog.load(p)
    assert [q.base for q in log2.topics["t"].partitions] == [6, 6]
    assert log2.committed("t", "g", 0) == 6
    # round-robin cursor survives: next keyless produce keeps balance
    log2.topics["t"].produce({"i": 12})
    log2.topics["t"].produce({"i": 13})
    assert [len(q) for q in log2.topics["t"].partitions] == [1, 1]


# ---------------------------------------------------------------------------
# index checkpoint / restore
# ---------------------------------------------------------------------------

def _loaded_index(n_shards, n_files=400):
    table = synth_filesystem(n_files, n_users=8, n_groups=4, n_dirs=24,
                             seed=3)
    idx = (PrimaryIndex() if n_shards is None
           else ShardedPrimaryIndex(n_shards))
    idx.ingest_table(table, version=5)
    # churn: tombstones + a newer-version overwrite, then compact a bit
    live = idx.live()
    kill = list(live["path"][:50])
    idx.delete_batch(kill, np.full(len(kill), 7, np.int64))
    idx.upsert_batch([str(live["path"][60])],
                     {"path_hash": live["path_hash"][60:61],
                      "size": np.array([123.0], np.float32)},
                     np.array([9], np.int64))
    return idx


@pytest.mark.parametrize("n_shards", [None, 1, 4])
def test_index_checkpoint_roundtrip(n_shards, tmp_path):
    idx = _loaded_index(n_shards)
    p = str(tmp_path / "idx.ckpt")
    idx.checkpoint(p, meta={"note": "barrier"})
    got = (PrimaryIndex.restore(p) if n_shards is None
           else ShardedPrimaryIndex.restore(p))
    a, b = idx.live(), got.live()
    assert set(a) == set(b)
    for k in a:
        assert np.array_equal(np.sort(a[k]), np.sort(b[k])), k
    # versions + liveness survive exactly (spot-check via lookups)
    for path in a["path"][:40]:
        assert got.lookup(str(path)) == idx.lookup(str(path))
    # tombstone floor + dead slots survive
    assert got.slot_stats() == idx.slot_stats()
    # dispatch helper rebuilds either layout
    from repro.core.index import read_blob
    again = index_from_state(read_blob(p)["state"])
    assert len(again) == len(idx)


def test_sharded_restore_rejects_layout_mismatch(tmp_path):
    idx = _loaded_index(4)
    p = str(tmp_path / "idx.ckpt")
    idx.checkpoint(p)
    other = ShardedPrimaryIndex(2)
    from repro.core.index import read_blob
    with pytest.raises(ValueError, match="shards"):
        other.load_state(read_blob(p)["state"])


def test_checkpoint_write_is_atomic(tmp_path):
    """A crash between the tmp write and the publish leaves the previous
    checkpoint readable — restores never see a torn file."""
    idx = _loaded_index(None)
    p = str(tmp_path / "idx.ckpt")
    idx.checkpoint(p)
    before = len(PrimaryIndex.restore(p))
    idx.delete_batch([str(idx.live()["path"][0])],
                     np.array([99], np.int64))

    from repro.core.index import atomic_write_blob

    class Torn(Exception):
        pass

    def boom():
        raise Torn()

    with pytest.raises(Torn):
        atomic_write_blob(p, {"state": idx.state_dict(), "meta": None},
                          pre_replace=boom)
    assert len(PrimaryIndex.restore(p)) == before      # old file intact


# ---------------------------------------------------------------------------
# property-based offset semantics (hypothesis)
# ---------------------------------------------------------------------------

def _create_batch(fids):
    b = ev.empty_batch(len(fids))
    f = np.asarray(fids)
    b["seq"] = f.astype(np.int64)
    b["etype"][:] = ev.E_CREAT
    b["fid"] = f.astype(np.int32)
    b["parent_fid"][:] = 0
    b["has_stat"][:] = 1
    b["size"] = (f % 97).astype(np.float32)
    b["mtime"] = (f % 31).astype(np.float32)
    b["uid"] = (f % 5 + 1).astype(np.int32)
    b["gid"] = (f % 3 + 1).astype(np.int32)
    return b


def _fresh(mode, log, n_partitions):
    primary = PrimaryIndex()
    ing = EventIngestor(
        IngestConfig(mode=mode, pad_to=64, max_buffer_events=40,
                     freshness_window=1e9, update_aggregates=False),
        PCFG, primary, AggregateIndex(), names={0: "fs"})
    pipe = DurablePipeline(log, ing, n_partitions=n_partitions,
                           batch_size=32)
    return primary, ing, pipe


@settings(max_examples=25, deadline=None)
@given(st.lists(st.sampled_from(["produce", "pump", "flush", "crash"]),
                min_size=1, max_size=24),
       st.sampled_from(["eager", "buffered"]),
       st.integers(1, 3))
def test_offset_interleavings_never_skip_or_double_commit(
        ops, mode, n_partitions):
    """Drive a random interleaving of produce / pump / flush / crash.
    Invariants checked throughout: committed offsets are monotone
    (never double-commit backwards), bounded by produced ends (never
    commit the future), and commit implies applied. At the end the
    index holds exactly the produced subjects (nothing skipped), and a
    full redelivery from offset zero changes nothing (idempotent
    replay)."""
    log = EventLog()
    primary, ing, pipe = _fresh(mode, log, n_partitions)
    next_fid = 1
    produced = {}
    names = {0: "fs"}
    last_committed = {p: 0 for p in range(n_partitions)}

    def check_commits():
        for p in range(n_partitions):
            c = log.committed(pipe.topic_name, pipe.group, p)
            assert c >= last_committed[p], "commit moved backwards"
            assert c <= pipe.topic.partitions[p].end, "committed the future"
            last_committed[p] = c

    for op in ops:
        if op == "produce":
            fids = list(range(next_fid, next_fid + 17))
            next_fid += 17
            fresh = {f: f"f{f}" for f in fids}
            names.update(fresh)
            produced.update(fresh)
            pipe.produce(_create_batch(fids), names=fresh)
        elif op == "pump":
            pipe.pump()
        elif op == "flush":
            pipe.flush()
        else:                              # crash: lose all volatile state
            primary, ing, pipe = _fresh(mode, log, n_partitions)
        check_commits()

    pipe.drain()
    check_commits()
    want = sorted(f"/fs/f{f}" for f in produced)
    got = sorted(str(p) for p in primary.live_paths())
    assert got == want                     # nothing skipped, nothing extra

    # maximal redelivery: replay EVERYTHING from offset zero again
    live_before = primary.live()
    for c in pipe.consumers:
        c.seek(pipe.topic.partitions[c.partition].base)
    pipe.drain()
    live_after = primary.live()
    order_b = np.argsort(live_before["path"])
    order_a = np.argsort(live_after["path"])
    for k in live_before:
        assert np.array_equal(live_before[k][order_b],
                              live_after[k][order_a]), k


def test_operator_truncate_respects_checkpoint_hold():
    """A broker-level truncate (default barrier) between checkpoints
    must not retire records above the pipeline's checkpoint barrier:
    committed offsets acknowledge applies that are durable only at the
    next checkpoint, so recovery still needs that suffix."""
    import tempfile
    log = EventLog()
    primary, ing, pipe = _fresh("eager", log, 2)
    names = {0: "fs", **{f: f"f{f}" for f in range(1, 40)}}
    pipe.produce(_create_batch(list(range(1, 20))), names=names)
    pipe.drain()
    ckpt = os.path.join(tempfile.mkdtemp(), "p.ckpt")
    barrier = pipe.checkpoint(ckpt)
    # more events: applied AND committed, but not yet checkpointed
    pipe.produce(_create_batch(list(range(20, 40))))
    pipe.drain()
    log.truncate(pipe.topic_name)        # operator/retention sweep
    for c in pipe.consumers:             # hold kept the suffix readable
        assert pipe.topic.partitions[c.partition].base \
            <= barrier[c.partition]
    # crash + restore from the pre-truncate checkpoint still recovers
    primary2, ing2, pipe2 = _fresh("eager", log, 2)
    pipe2.load_checkpoint(ckpt)
    pipe2.drain()
    assert sorted(map(str, primary2.live_paths())) == \
        sorted(map(str, primary.live_paths()))


def test_names_only_produce_is_durable():
    """Name bindings published with an EMPTY batch must survive a crash:
    they ride a names-only payload into the log, so a rebuilt consumer
    resolves later events without '#fid' fallbacks."""
    log = EventLog()
    _, _, pipe = _fresh("eager", log, 2)
    pipe.produce(ev.empty_batch(0), names={0: "fs", 7: "f7"})
    pipe.pump()           # names-only payloads must not crash the pump
    # crash: fresh volatile state, same log
    primary, ing, pipe = _fresh("eager", log, 2)
    assert pipe.pump() == {"read": 0, "applied": 0}   # names-only redelivery
    b = _create_batch([7])
    pipe.produce(b)
    pipe.drain()
    assert [str(p) for p in primary.live_paths()] == ["/fs/f7"]
    assert ing.metrics["unresolved"] == 0


# ---------------------------------------------------------------------------
# freshness threading: log lag next to the watermark
# ---------------------------------------------------------------------------

def test_log_lag_threaded_into_freshness_and_merge():
    log = EventLog()
    primary, ing, pipe = _fresh("eager", log, 2)
    pipe.produce(_create_batch(list(range(1, 33))),
                 names={f: f"f{f}" for f in range(1, 33)})
    fr = ing.freshness()
    assert fr["log_lag"] == pipe.lag() > 0      # produced, not consumed
    pipe.drain()
    fr = ing.freshness()
    assert fr["log_lag"] == 0 and fr["applied_seq"] == 32

    from repro.core.query import QueryEngine, merge_freshness
    merged = merge_freshness([ing.freshness(), {**ing.freshness(),
                                                "log_lag": 5}])
    assert merged["log_lag"] == 5
    q = QueryEngine(primary, AggregateIndex(), now=1.7e9, ingestor=ing)
    assert q.query("stat", "/fs/f1")["freshness"]["log_lag"] == 0


# ---------------------------------------------------------------------------
# restore resets producer routing exactly (ISSUE 9 satellite)
# ---------------------------------------------------------------------------

def test_restore_resets_producer_routing_to_checkpoint_bindings():
    """Rolling a pipeline back to an earlier checkpoint must leave the
    producer routing table with EXACTLY the restored bindings. The old
    ``update`` merge kept post-checkpoint bindings alive, so a produce
    for such a fid routed by its (stale) name while a fresh process
    restoring the same checkpoint routed by the ``#fid`` fallback —
    divergent partition placement for the same event."""
    import tempfile
    from repro.core.sharded_index import path_hashes
    log = EventLog()
    primary, ing, pipe = _fresh("eager", log, 4)
    pipe.produce(_create_batch([1, 2, 3]),
                 names={0: "fs", 1: "f1", 2: "f2", 3: "f3"})
    pipe.drain()
    ckpt = os.path.join(tempfile.mkdtemp(), "p.ckpt")
    pipe.checkpoint(ckpt)
    # a binding the checkpoint has never seen, whose name routes to a
    # DIFFERENT partition than the '#fid' fallback a fresh process uses
    fid, name = next(
        (f, f"zz{f}")
        for f in range(50, 200)
        if int(path_hashes([f"zz{f}"])[0]) % 4
        != int(path_hashes([f"#{f}"])[0]) % 4)
    pipe.produce(_create_batch([fid]), names={fid: name})
    assert pipe._prod_names[fid] == name
    # roll back: the restored table must match the checkpoint exactly
    pipe.load_checkpoint(ckpt)
    assert fid not in pipe._prod_names
    assert pipe._prod_names == dict(ing._name)
    assert pipe._pending_names == {}
    # and post-restore produce places the event where a FRESH process
    # restoring the same checkpoint would (the '#fid' route)
    ends_before = [p.end for p in pipe.topic.partitions]
    pipe.produce(_create_batch([fid]))
    grew = [i for i, p in enumerate(pipe.topic.partitions)
            if p.end > ends_before[i]]
    assert grew == [int(path_hashes([f"#{fid}"])[0]) % 4]


# ---------------------------------------------------------------------------
# the log leg: the same changelog through the log or fed directly
# ---------------------------------------------------------------------------

def _tree_and_creates(n_files, n_dirs=64, batch=512, seed=1):
    """A random directory tree (one mkdir batch), then stat-carrying
    creates under it in ``batch``-sized changelog batches."""
    rng = np.random.default_rng(seed)
    dfids = np.arange(1, n_dirs + 1)
    b = ev.empty_batch(n_dirs)
    b["seq"] = dfids.astype(np.int64)
    b["etype"][:] = ev.E_MKDIR
    b["fid"] = dfids.astype(np.int32)
    b["parent_fid"][1:] = rng.integers(0, dfids[:-1] + 1).astype(np.int32)
    b["is_dir"][:] = 1
    batches = [b]
    names = {0: "fs", **{int(d): f"d{d}" for d in dfids}}
    ffids = np.arange(n_dirs + 1, n_dirs + 1 + n_files)
    names.update({int(f): f"f{f}" for f in ffids})
    for lo in range(0, n_files, batch):
        f = ffids[lo:lo + batch]
        bb = ev.empty_batch(len(f))
        bb["seq"] = f.astype(np.int64)
        bb["etype"][:] = ev.E_CREAT
        bb["fid"] = f.astype(np.int32)
        bb["parent_fid"] = rng.integers(1, n_dirs + 1, len(f)).astype(np.int32)
        bb["has_stat"][:] = 1
        bb["size"] = rng.gamma(1.5, 1e4, len(f)).astype(np.float32)
        bb["mtime"] = rng.uniform(1, 1e6, len(f)).astype(np.float32)
        bb["uid"] = rng.integers(0, PCFG.n_users, len(f)).astype(np.int32)
        bb["gid"] = (bb["uid"] % PCFG.n_groups).astype(np.int32)
        batches.append(bb)
    return batches, names


def test_log_leg_loses_no_record():
    """Produce -> partitioned log -> consumer group -> commit-after-apply
    leaves a 4-shard index byte-identical to feeding the ingestor the
    same batches directly, with the same applied watermark and no lag."""
    batches, names = _tree_and_creates(6000)

    def ingestor():
        primary = ShardedPrimaryIndex(4)
        return primary, EventIngestor(
            IngestConfig(mode="eager", pad_to=512, update_aggregates=False),
            PCFG, primary, AggregateIndex())

    direct, ing_direct = ingestor()
    for k, b in enumerate(batches):
        ing_direct.ingest(b, names=names if k == 0 else None)
    logged, ing_log = ingestor()
    pipe = DurablePipeline(EventLog(), ing_log, n_partitions=4,
                           batch_size=512)
    for k, b in enumerate(batches):
        pipe.produce(b, names=names if k == 0 else None)
    pipe.drain()
    assert len(logged) == len(direct) == 6000
    assert_byte_identical(logged.live(), direct.live(), "log leg")
    assert ing_log.freshness()["applied_seq"] == \
        ing_direct.freshness()["applied_seq"]
    assert pipe.lag() == 0
    assert ing_log.metrics["unresolved"] == 0     # every path resolved
