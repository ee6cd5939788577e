"""ShardedPrimaryIndex (core/sharded_index.py): routing, slot maps,
scatter-gather queries, cross-shard rename migration, and freshness
semantics (ISSUE 2).

The load-bearing contract: a sharded index is OBSERVATIONALLY IDENTICAL
to the monolith — same live set, same column values, same query results
— with partitioning visible only through performance and the per-shard
diagnostics surface.
"""
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import events as ev
from repro.core import snapshot as snap
from repro.core.event_ingest import EventIngestor, IngestConfig
from repro.core.index import AggregateIndex, DictSlotMap, PrimaryIndex
from repro.core.metadata import path_hash, synth_filesystem
from repro.core.monitor import MonitorConfig, MonitorPool
from repro.core.query import QueryEngine, merge_freshness
from repro.core.sharded_index import (HashSlotMap, ShardedPrimaryIndex,
                                      path_hashes, shard_of)

PCFG = snap.PipelineConfig(n_users=8, n_groups=4, n_dirs=16)


def sorted_live(idx):
    live = idx.live()
    order = np.argsort(live["path"])
    return {k: v[order] for k, v in live.items()}


def assert_same_live(a, b):
    la, lb = sorted_live(a), sorted_live(b)
    assert set(la) == set(lb)
    for k in la:
        if k == "version":
            continue
        assert np.array_equal(la[k], lb[k]), k


# ---------------------------------------------------------------------------
# routing: one FNV family everywhere
# ---------------------------------------------------------------------------

def test_path_hashes_matches_scalar_fnv():
    paths = ["/fs", "", "/fs/a/b.c", "/" + "x" * 300, "/fs/d1/f99"]
    got = path_hashes(paths)
    assert got.dtype == np.uint32
    assert [int(h) for h in got] == [path_hash(p) for p in paths]


def test_route_batch_matches_singleton_fallback():
    idx = ShardedPrimaryIndex(5, kernel_route_min=1 << 30)
    paths = [f"/fs/d{i % 7}/f{i}" for i in range(200)]
    _, sids = idx.route(paths)
    assert [int(s) for s in sids] == [idx.shard_of(p) for p in paths]
    assert all(shard_of(p, 5) == idx.shard_of(p) for p in paths[:20])


def test_device_route_matches_host_route():
    """The hashshard op (kernel or its jitted oracle) and the host
    fallback put every path in the same shard — including paths longer
    than the packing width (patched through the scalar hash)."""
    idx = ShardedPrimaryIndex(7, kernel_route_min=1, route_width=32)
    paths = [f"/fs/d{i}/f{i}" for i in range(64)] + ["/fs/" + "q" * 100]
    h_dev = idx._route_device(paths)
    assert [int(h) for h in h_dev] == [path_hash(p) for p in paths]


STAGE_SPANS = {"index.route.encode", "index.route.device",
               "index.upsert.split", "index.upsert.assign",
               "index.upsert.write"}


@pytest.mark.parametrize("route_first", [True, False])
def test_route_and_upsert_record_their_stage_spans(route_first):
    """A device-routed batch records every stage span of route and
    upsert, each a leaf: their sum never exceeds the elapsed time, also
    when ``upsert_batch`` hashes the paths itself."""
    import time

    from repro.core.telemetry import Telemetry
    tel = Telemetry()
    idx = ShardedPrimaryIndex(4, kernel_route_min=8, route_width=64,
                              telemetry=tel)
    assert all(sh.telemetry is tel for sh in idx.shards)
    paths = [f"/fs/d{i % 7}/f{i}" for i in range(300)]
    sizes = np.arange(300, dtype=np.float32)
    t0 = time.perf_counter()
    if route_first:
        h, _ = idx.route(paths)
        idx.upsert_batch(paths, {"size": sizes, "path_hash": h},
                         np.ones(300, np.int64), hashes=h)
    else:
        idx.upsert_batch(paths, {"size": sizes}, np.ones(300, np.int64))
    elapsed = time.perf_counter() - t0
    fam = tel.snapshot(traces=False)["metrics"]["span_seconds_total"]
    got = {s["labels"]["span"]: s["value"] for s in fam["series"]}
    assert set(got) == STAGE_SPANS
    assert all(v > 0 for v in got.values())
    assert sum(got.values()) <= elapsed
    assert len(idx) == 300


def test_pallas_kernel_route_parity():
    """The actual Pallas kernel (interpret mode) agrees with the jnp
    oracle the CPU routing path uses."""
    from repro.kernels.hashshard import ops as hs_ops
    from repro.kernels.hashshard.hashshard import hashshard_pallas
    from repro.kernels.hashshard.ref import encode_strings_np
    paths = [f"/fs/d{i % 5}/f{i}" for i in range(64)]
    rows, lens, trunc = encode_strings_np(paths, 64)
    assert not trunc.any()
    h_k, s_k = hashshard_pallas(rows, lens, 7, interpret=True)
    h_o, s_o = hs_ops.hashshard_route(rows, lens, 7)
    np.testing.assert_array_equal(np.asarray(h_k), np.asarray(h_o))
    np.testing.assert_array_equal(np.asarray(s_k), np.asarray(s_o))


def test_encode_strings_np_matches_loop_encoder():
    from repro.kernels.hashshard.ref import encode_strings, encode_strings_np
    paths = ["/fs/a", "", "/fs/" + "y" * 50, "/fs/d2/f9"]
    rows_l, lens_l = encode_strings(paths, 16)
    rows_v, lens_v, trunc = encode_strings_np(paths, 16)
    np.testing.assert_array_equal(rows_l, rows_v)
    np.testing.assert_array_equal(lens_l, lens_v)
    assert trunc.tolist() == [False, False, True, False]


def _route_cases():
    import os
    return {
        "ascii": [f"/fs/d{i % 7}/f{i}" for i in range(40)],
        "utf8": [f"/fs/dé{i}/ファイル{i}.dat" for i in range(40)],
        "surrogate": [os.fsdecode(b"/fs/raw\xff\xfe%d" % i)
                      for i in range(40)],
        "empty": ["", "/fs/a", "", "/fs/b/c", ""],
        "nul": ["/fs/a\0b", "/fs/c", "/fs/d/e"],
        "over_width": ["/fs/a", "/fs/" + "z" * 300, "/fs/é" * 70],
    }


@pytest.mark.parametrize("case", sorted(_route_cases()))
def test_joined_encode_route_matches_path_hash(case):
    """The device route, fed by the joined-buffer encoder, equals the
    scalar hash for ASCII, multi-byte UTF-8, lone surrogates from
    ``os.fsdecode``, empty strings, a path holding a NUL (the fallback
    encoder) and paths longer than ``route_width`` (patched)."""
    from repro.kernels.hashshard.ref import encode_strings_joined
    paths = _route_cases()[case]
    idx = ShardedPrimaryIndex(5, kernel_route_min=1)
    rows, lens, trunc = encode_strings_joined(paths, idx.route_width)
    for i, p in enumerate(paths):
        raw = p.encode("utf-8", "surrogatepass")
        assert lens[i] == min(len(raw), idx.route_width)
        assert bytes(rows[i, :lens[i]]) == raw[:lens[i]]
        assert trunc[i] == (len(raw) > idx.route_width)
    assert [int(h) for h in idx._route_device(paths)] == [
        path_hash(p) for p in paths]


@pytest.mark.parametrize("longest,width", [
    (1, 32), (31, 32), (32, 32), (33, 64), (64, 64), (65, 128),
    (128, 128), (129, 192), (192, 192), (193, 192)])
def test_joined_encode_width_bucket(longest, width):
    """Rows are cut at the power of two at or above the batch's longest
    path, at least 32 and at most ``route_width`` (192); hashes equal
    the scalar hash on both sides of every bucket edge."""
    from repro.kernels.hashshard.ref import encode_strings_joined
    paths = [f"/f{i}"[:longest] for i in range(63)] + ["/" * longest]
    rows, lens, trunc = encode_strings_joined(paths, 192)
    assert rows.shape == (64, width)
    assert int(lens.max()) == min(longest, 192)
    assert trunc.tolist() == [False] * 63 + [longest > 192]
    idx = ShardedPrimaryIndex(3, kernel_route_min=1)
    assert [int(h) for h in idx._route_device(paths)] == [
        path_hash(p) for p in paths]


def test_joined_encode_list_and_ndarray_agree():
    from repro.kernels.hashshard.ref import encode_strings_joined
    paths = [f"/fs/d{i % 3}/é{i}" for i in range(50)] + [""]
    got_l = encode_strings_joined(paths, 64)
    got_a = encode_strings_joined(np.asarray(paths, object), 64)
    for a, b in zip(got_l, got_a):
        np.testing.assert_array_equal(a, b)


def test_route_rows_counter_counts_device_rows_by_width():
    """``index_route_rows_total{width}`` counts each device-routed row
    once, under its batch's width bucket; host-routed batches and
    batches that carry hashes leave it untouched."""
    from repro.core.telemetry import Telemetry
    tel = Telemetry()
    idx = ShardedPrimaryIndex(4, kernel_route_min=16, telemetry=tel)

    def counts():
        fam = tel.snapshot(traces=False)["metrics"][
            "index_route_rows_total"]
        return {s["labels"]["width"]: s["value"] for s in fam["series"]
                if s["value"]}

    short = [f"/fs/f{i}" for i in range(20)]
    idx.route(short[:10])
    idx.route(short, hashes=path_hashes(short))
    assert counts() == {}
    idx.route(short)
    idx.route(short[:17] + ["/fs/" + "x" * 60])
    idx.route(short[:16] + ["/fs/" + "y" * 200])
    assert counts() == {"32": 20, "64": 18, "192": 17}


# ---------------------------------------------------------------------------
# HashSlotMap == DictSlotMap (behavioral parity)
# ---------------------------------------------------------------------------

def slot_partition(slots):
    groups = {}
    for i, s in enumerate(slots):
        groups.setdefault(int(s), []).append(i)
    return sorted(map(tuple, groups.values()))


@pytest.mark.parametrize("rebuild_min", [4, 8192])
def test_hash_slot_map_parity(rebuild_min):
    """assign/lookup/get/get_or_add behave exactly like the dict map —
    including in-batch duplicates, incremental batches, and overlay
    folds (tiny rebuild_min forces folds mid-stream)."""
    pytest.importorskip("pandas")
    rng = np.random.default_rng(0)
    pool = [f"/fs/d{i % 37}/f{i}" for i in range(300)]
    d, h = DictSlotMap(), HashSlotMap(rebuild_min=rebuild_min)
    for batch_no in range(6):
        batch = [pool[int(rng.integers(300))] for _ in range(100)] \
            + [f"/new{batch_no}/f{i}" for i in range(40)]
        sd, nd = d.assign(batch)
        sh, nh = h.assign(batch)
        assert np.array_equal(nd, nh), batch_no
        assert len(d) == len(h)
        probe = batch[::3] + ["/absent/x", "/absent/y"]
        assert np.array_equal(d.lookup(probe) == -1, h.lookup(probe) == -1)
    # full-map partition equivalence: same subjects share slots
    allp = pool + [f"/new{b}/f{i}" for b in range(6) for i in range(40)]
    assert slot_partition(d.assign(allp)[0]) \
        == slot_partition(h.assign(allp)[0])
    assert h.get("/absent/z") is None
    s1, new1 = h.get_or_add("/solo/a")
    s2, new2 = h.get_or_add("/solo/a")
    assert new1 and not new2 and s1 == s2 == h.get("/solo/a")


# ---------------------------------------------------------------------------
# sharded == monolith (snapshot paths)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_shards", [1, 3, 8])
def test_ingest_table_matches_monolith(n_shards):
    table = synth_filesystem(3000, n_dirs=150, seed=2)
    mono, shd = PrimaryIndex(), ShardedPrimaryIndex(n_shards)
    assert mono.ingest_table(table, 1) == shd.ingest_table(table, 1)
    assert len(mono) == len(shd)
    assert_same_live(mono, shd)
    # idempotent re-ingest at a later version
    mono.ingest_table(table, 9)
    shd.ingest_table(table, 9)
    assert_same_live(mono, shd)
    # shards are actually populated (hash balance, not one hot shard)
    if n_shards > 1:
        assert (shd.shard_sizes() > 0).all()


def test_ingest_tables_presplit_matches_monolith():
    """The partitioned scan feed (snapshot.split_table_by_shard ->
    ingest_tables) produces the same index as routing inside
    ingest_table — and as the monolith."""
    table = synth_filesystem(3000, n_dirs=150, seed=3)
    mono = PrimaryIndex()
    mono.ingest_table(table, 1)
    pre = ShardedPrimaryIndex(4)
    pre.ingest_tables(snap.split_table_by_shard(table, 4), 1)
    routed = ShardedPrimaryIndex(4)
    routed.ingest_table(table, 1)
    assert_same_live(mono, pre)
    assert_same_live(pre, routed)


def test_snapshot_absence_tombstones_all_shards():
    """A re-scan at a later version kills records the scan no longer
    contains — in EVERY shard, including shards the new scan assigns no
    rows (invalidate_older must fan out)."""
    t1 = synth_filesystem(400, n_dirs=40, seed=4)
    shd = ShardedPrimaryIndex(4)
    shd.ingest_table(t1, 1)
    n1 = len(shd)
    # second scan: one single file survives -> 3+ shards get no rows
    files = t1.select(t1.type != 2)
    keep = files.select(np.arange(len(files)) == 0)
    shd.ingest_table(keep, 2)
    assert n1 > 1 and len(shd) == 1


# ---------------------------------------------------------------------------
# event path: migration between shards via rename
# ---------------------------------------------------------------------------

def test_rename_migrates_record_between_shards():
    """A dir rename that changes a record's subject hash moves it to a
    different shard as a delete+upsert pair: exactly one live record
    afterwards, in the new shard, with the old shard's copy dead."""
    shd = ShardedPrimaryIndex(2)
    ing = EventIngestor(
        IngestConfig(pad_to=64, update_aggregates=False), PCFG,
        shd, AggregateIndex(), names={0: "fs"})
    s = ev.EventStream(start_fid=1)
    d1 = s.alloc_fid()
    s.emit(ev.E_MKDIR, d1, 0, is_dir=1, name=f"d{d1}")
    f = s.alloc_fid()
    # find a destination dir name whose resulting subject hash lands in
    # the OTHER shard
    s.emit(ev.E_CREAT, f, d1, has_stat=1, size=5.0, uid=1, gid=1,
           name=f"f{f}")
    ing.ingest(s.take(), names=s.names)
    old_path = f"/fs/d{d1}/f{f}"
    old_shard = shd.shard_of(old_path)
    d2 = None
    for cand in range(100, 200):
        if shd.shard_of(f"/fs/e{cand}/f{f}") != old_shard:
            d2 = cand
            break
    assert d2 is not None
    dfid = s.alloc_fid()
    s.emit(ev.E_MKDIR, dfid, 0, is_dir=1, name=f"e{d2}")
    s.emit(ev.E_RENME, d1, 0, dfid, is_dir=1)   # mv /fs/d1 /fs/e<d2>/d1
    ing.ingest(s.take(), names=s.take_names())
    new_path = f"/fs/e{d2}/d{d1}/f{f}"
    assert sorted(shd.live()["path"]) == [new_path]
    assert shd.shard_of(new_path) != old_shard
    assert len(shd.shards[old_shard]) == 0          # tombstoned
    assert len(shd.shards[shd.shard_of(new_path)]) == 1
    rec = shd.lookup(new_path)
    assert rec is not None and rec["size"] == 5.0   # stat survived


# ---------------------------------------------------------------------------
# scatter-gather queries: property-based equivalence with the monolith
# ---------------------------------------------------------------------------

def engines(seed, n_shards, n_files=800):
    table = synth_filesystem(n_files, n_dirs=60, seed=seed)
    mono, shd = PrimaryIndex(), ShardedPrimaryIndex(n_shards)
    mono.ingest_table(table, 1)
    shd.ingest_table(table, 1)
    agg = AggregateIndex()
    return (QueryEngine(mono, agg), QueryEngine(shd, agg),
            table.paths[table.type != 2])


def paths_equal(a, b):
    return sorted(map(str, a)) == sorted(map(str, b))


@settings(max_examples=6, deadline=None)
@given(st.integers(0, 10_000), st.sampled_from([1, 2, 3, 5, 8]))
def test_query_equivalence_property(seed, n_shards):
    """Every primary-index query returns identical results on the
    sharded index (any shard count) and the monolith."""
    qm, qs, file_paths = engines(seed, n_shards)
    assert paths_equal(qm.find_by_name(r"f\d*7$"),
                       qs.find_by_name(r"f\d*7$"))
    assert paths_equal(qm.world_writable(), qs.world_writable())
    assert paths_equal(qm.not_accessed_since(90 * 86400),
                       qs.not_accessed_since(90 * 86400))
    assert paths_equal(qm.large_cold_files(1e5, 30 * 86400),
                       qs.large_cold_files(1e5, 30 * 86400))
    assert paths_equal(qm.owned_by_deleted_users(range(4)),
                       qs.owned_by_deleted_users(range(4)))
    assert paths_equal(qm.past_retention(365 * 86400),
                       qs.past_retention(365 * 86400))
    dm, ds = qm.duplicate_candidates(), qs.duplicate_candidates()
    assert set(dm) == set(ds)
    for k in dm:
        assert paths_equal(dm[k], ds[k])
    assert qm.most_small_files(5) == qs.most_small_files(5)
    # point lookups route to one shard and agree with the monolith
    rng = np.random.default_rng(seed)
    for p in rng.choice(file_paths, size=5, replace=False):
        assert qm.stat(p) == qs.stat(p)
    assert qs.stat("/fs/never/indexed") is None


def test_sharded_live_schema_stable():
    """live() on a sharded index carries every STANDARD_COLUMNS key plus
    path, with the documented dtypes — even when some shards are empty
    or were never written."""
    shd = ShardedPrimaryIndex(8)
    shd.upsert_batch(["/fs/only/one"],
                     {"path_hash": np.array([path_hash("/fs/only/one")],
                                            np.uint32),
                      "size": np.array([3.0], np.float32)},
                     np.array([1]))
    live = shd.live()
    assert len(live["path"]) == 1
    for k, dt in PrimaryIndex.STANDARD_COLUMNS.items():
        assert k in live and live[k].dtype == dt, k
    empty = ShardedPrimaryIndex(3).live()
    assert len(empty["path"]) == 0
    for k in PrimaryIndex.STANDARD_COLUMNS:
        assert k in empty


# ---------------------------------------------------------------------------
# find_by_name: path-only scan regression (100k corpus)
# ---------------------------------------------------------------------------

def test_find_by_name_scans_paths_only_at_100k():
    """find_by_name on a 100k-path index must (a) return exactly the
    regex matches and (b) never materialize the full live() view — the
    fix for the per-query all-columns copy."""
    table = synth_filesystem(100_000, n_dirs=1000, seed=0)
    idx = PrimaryIndex()
    idx.ingest_table(table, 1)
    q = QueryEngine(idx, AggregateIndex())
    import re
    want = sorted(p for p in idx.live_paths() if re.search(r"f1\d\d$", p))
    idx.live = lambda: (_ for _ in ()).throw(
        AssertionError("find_by_name must not materialize live()"))
    got = q.find_by_name(r"f1\d\d$")
    assert sorted(map(str, got)) == want
    assert 0 < len(got) < 2000


# ---------------------------------------------------------------------------
# freshness semantics: pending counts, monotonicity, min-over-shards
# ---------------------------------------------------------------------------

def make_buffered(primary, t):
    return EventIngestor(
        IngestConfig(mode="buffered", freshness_window=5.0,
                     max_buffer_events=1000, pad_to=64,
                     update_aggregates=False),
        PCFG, primary, AggregateIndex(), names={0: "fs"},
        clock=lambda: t["now"])


def test_buffered_pending_counts_with_sharded_primary():
    t = {"now": 0.0}
    shd = ShardedPrimaryIndex(3)
    ing = make_buffered(shd, t)
    s = ev.EventStream(start_fid=1)
    for i in range(4):
        f = s.alloc_fid()
        s.emit(ev.E_CREAT, f, 0, has_stat=1, size=1.0, name=f"f{f}")
    ing.ingest(s.take(2), names=s.names)
    assert ing.freshness()["pending_events"] == 2
    ing.ingest(s.take(), names=s.names)
    assert ing.freshness()["pending_events"] == 4
    assert len(shd) == 0                 # nothing visible yet
    t["now"] = 6.0
    assert ing.tick() == 4
    fr = ing.freshness()
    assert fr["pending_events"] == 0 and fr["applied_seq"] == 4
    assert len(shd) == 4


@pytest.mark.parametrize("n_shards", [None, 3])
def test_watermark_monotone_across_applies(n_shards):
    primary = (PrimaryIndex() if n_shards is None
               else ShardedPrimaryIndex(n_shards))
    ing = EventIngestor(
        IngestConfig(pad_to=64, update_aggregates=False), PCFG,
        primary, AggregateIndex(), names={0: "fs"})
    s = ev.EventStream(start_fid=1)
    seen = [ing.watermark.applied_seq]
    batchnos = [ing.watermark.applied_batches]
    for i in range(6):
        f = s.alloc_fid()
        s.emit(ev.E_CREAT, f, 0, has_stat=1, size=1.0, name=f"f{f}")
        if i % 2:
            s.emit(ev.E_UNLNK, f, 0)
        ing.ingest(s.take(), names=s.names)
        seen.append(ing.watermark.applied_seq)
        batchnos.append(ing.watermark.applied_batches)
    assert seen == sorted(seen) and seen[-1] > 0
    assert batchnos == sorted(batchnos) and batchnos[-1] == 6
    # replaying old events never regresses the watermark
    old = ing.watermark.applied_seq
    s2 = ev.EventStream(start_fid=100)
    f = s2.alloc_fid()
    s2.emit(ev.E_CREAT, f, 0, has_stat=1, size=1.0, name=f"f{f}")
    b = s2.take()
    b["seq"][:] = 1                      # stale seq
    ing.ingest(b, names=s2.names)
    assert ing.watermark.applied_seq >= old


def test_min_over_shards_freshness_in_monitor_pool():
    """MonitorPool freshness = min applied_seq / sum pending over the
    per-partition ingestors (paper §IV-B4 + DESIGN.md §8)."""
    t = {"now": 0.0}
    shd = ShardedPrimaryIndex(2)
    ing_a, ing_b = make_buffered(shd, t), make_buffered(shd, t)
    pool = MonitorPool(2, MonitorConfig(max_fids=512, batch_size=64),
                       ingestors=[ing_a, ing_b])
    sa, sb = ev.EventStream(start_fid=1), ev.EventStream(start_fid=500)
    for i in range(3):
        f = sa.alloc_fid()
        sa.emit(ev.E_CREAT, f, 0, has_stat=1, size=1.0, name=f"f{f}")
    for i in range(5):
        f = sb.alloc_fid()
        sb.emit(ev.E_CREAT, f, 0, has_stat=1, size=1.0, name=f"g{f}")
    ing_a.ingest(sa.take(), names=sa.names)
    ing_b.ingest(sb.take(), names=sb.names)
    ing_a.flush()                        # partition A applied; B pending
    fr = pool.freshness()
    assert fr["applied_seq"] == 0        # min over partitions: B at 0
    assert fr["pending_events"] == 5
    assert fr["sources"] == 2
    ing_b.flush()
    fr = pool.freshness()
    assert fr["applied_seq"] == 3 and fr["pending_events"] == 0
    # QueryEngine accepts the ingestor list and reports the same merge
    q = QueryEngine(shd, AggregateIndex(), ingestor=[ing_a, ing_b])
    assert q.freshness() == fr
    out = q.query("find_by_name", "f")
    assert out["freshness"]["applied_seq"] == 3
    # merge_freshness alone: None sources drop out; empty -> None
    assert merge_freshness([None, ing_a.freshness()])["applied_seq"] == 3
    assert merge_freshness([]) is None
