"""Concurrent query service over MVCC snapshots (ISSUE 6).

The centerpiece is a concurrent-read differential harness extending the
tests/test_differential.py oracle: a writer thread replays the random
event workload through the EventIngestor while reader threads take
``QueryService`` snapshots and run the Table-I query suite — and every
result must be byte-identical to the same query against a frozen deep
copy (``state_dict`` / ``index_from_state``) of the index captured at
that snapshot's watermark token. Run across the eager/buffered x
monolithic/4-shard matrix, with a discovery index attached so the
planner's prefilter -> exact-verify path serves from pinned snapshots
too.

The oracle protocol piggybacks on the MVCC write lock: the writer holds
``primary.write_lock()`` (reentrant) across each ingest AND the
state-dict capture, and ``QueryService.snapshot()`` pins under the same
lock — so every watermark token a reader can observe has exactly one
recorded oracle state.

Also here: the thread-local ``last_plan`` regression (two interleaved
planner queries must each see their own plan), result-cache accounting
(hit/miss, invalidation exactly on MUTATING watermark advance — a
coalesced-away all-OPEN batch advances the raw watermark but must NOT
drop the cache), cursor stability across ingest, and the snapshot-pin
leak check (closing everything returns arena refcounts to baseline and
disarms copy-on-write).
"""
import threading
import time

import numpy as np
import pytest

import test_differential as td
from repro.core import events as ev
from repro.core.discovery import rebuild_discovery
from repro.core.event_ingest import EventIngestor, IngestConfig
from repro.core.index import AggregateIndex, PrimaryIndex
from repro.core.metadata import files_only, synth_filesystem
from repro.core.query import QueryEngine
from repro.core.query_service import QueryService, ResultCache
from repro.core.sharded_index import ShardedPrimaryIndex, index_from_state

NOW = 2e6          # mtimes are uniform(1, 1e6): the cutoffs below split

#: the Table-I suite with args that discriminate on the workload's
#: distributions (primary-scan, planner, point, and aggregate families)
QUERIES = [
    ("find_by_name", (r"f\d*[02468]$",), {}),
    ("find_by_glob", ("/fs/*f*1*",), {}),
    ("world_writable", (), {}),
    ("not_accessed_since", (1.5e6,), {}),
    ("large_cold_files", (1e4, 1.7e6), {}),
    ("duplicate_candidates", (), {}),
    ("owned_by_deleted_users", ([0, 1, 2, 3],), {}),
    ("past_retention", (1.3e6,), {}),
    ("most_small_files", (), {}),
    ("per_user_usage", (), {}),
    ("storage_by_project", (), {}),
    ("dir_size_percentile", (), {}),
    ("directories_over", (100,), {}),
]


def assert_same_result(got, want, ctx=""):
    """Byte-identity across the suite's result shapes (arrays, dicts of
    arrays, lists of tuples, scalars)."""
    if isinstance(want, np.ndarray):
        assert isinstance(got, np.ndarray), ctx
        assert got.dtype == want.dtype, (ctx, got.dtype, want.dtype)
        assert np.array_equal(got, want), ctx
    elif isinstance(want, dict):
        assert set(got) == set(want), ctx
        for k in want:
            assert_same_result(got[k], want[k], (ctx, k))
    elif isinstance(want, (list, tuple)):
        assert len(got) == len(want), ctx
        for g, w in zip(got, want):
            assert_same_result(g, w, ctx)
    else:
        assert got == want, ctx


def build_workload(n_ops, seed):
    stream = ev.EventStream(start_fid=1)
    td.gen_workload(stream, n_ops, seed)
    names = {0: "fs", **stream.names}
    batches = []
    while len(stream):
        batches.append(stream.take(64))
    return batches, names


def make_service(mode, n_shards, names, discovery=False):
    primary = td.make_primary(n_shards)
    if discovery:
        rebuild_discovery(primary)
    ing = EventIngestor(
        IngestConfig(mode=mode, pad_to=64, max_buffer_events=150,
                     freshness_window=1e9, update_aggregates=False),
        td.PCFG, primary, AggregateIndex(), names=names)
    svc = QueryService(primary, AggregateIndex(), ingestor=ing, now=NOW)
    return primary, ing, svc


# ---------------------------------------------------------------------------
# the concurrent-read differential harness (the tentpole's proof)
# ---------------------------------------------------------------------------

def run_concurrent_differential(mode, n_shards, n_readers=3, n_ops=700,
                                seed=5):
    batches, names = build_workload(n_ops, seed)
    primary, ing, svc = make_service(mode, n_shards, names, discovery=True)

    oracle = {}                      # watermark token -> frozen state_dict
    with primary.write_lock():
        oracle[svc.data_version] = primary.state_dict()
    stop = threading.Event()
    errors = []
    checked = [0] * n_readers

    def writer():
        try:
            for b in batches:
                with primary.write_lock():
                    ing.ingest(b)
                    wm = svc.data_version
                    if wm not in oracle:
                        oracle[wm] = primary.state_dict()
                time.sleep(0.002)    # let readers interleave mid-stream
            with primary.write_lock():
                ing.flush()
                wm = svc.data_version
                if wm not in oracle:
                    oracle[wm] = primary.state_dict()
        except BaseException as e:   # pragma: no cover - diagnostic path
            errors.append(("writer", repr(e)))
        finally:
            stop.set()

    def reader(rid):
        rng = np.random.default_rng(1000 * rid + seed)
        try:
            while True:
                last_round = stop.is_set()
                with svc.snapshot() as snap:
                    wm = snap.watermark
                    state = oracle.get(wm)
                    assert state is not None, f"unrecorded watermark {wm}"
                    frozen = index_from_state(state)
                    want_eng = QueryEngine(frozen, AggregateIndex(),
                                           now=NOW)
                    for name, a, kw in QUERIES:
                        got = getattr(snap.engine, name)(*a, **kw)
                        want = getattr(want_eng, name)(*a, **kw)
                        assert_same_result(
                            got, want,
                            f"{name} wm={wm} mode={mode} "
                            f"shards={n_shards} reader={rid}")
                    # point probe on a live subject of the pinned state
                    paths = frozen.live_paths()
                    if len(paths):
                        p = str(paths[int(rng.integers(len(paths)))])
                        assert_same_result(snap.engine.stat(p),
                                           want_eng.stat(p),
                                           f"stat wm={wm}")
                # the cached service path must agree with the oracle at
                # whatever watermark IT pinned
                name, a, kw = QUERIES[int(rng.integers(len(QUERIES)))]
                r = svc.query(name, *a, **kw)
                wm2 = r["freshness"]["watermark"]
                want_eng2 = QueryEngine(index_from_state(oracle[wm2]),
                                        AggregateIndex(), now=NOW)
                assert_same_result(r["result"],
                                   getattr(want_eng2, name)(*a, **kw),
                                   f"service {name} wm={wm2}")
                checked[rid] += 1
                if last_round:
                    return
        except BaseException as e:
            errors.append((f"reader{rid}", repr(e)))

    threads = [threading.Thread(target=writer)] + [
        threading.Thread(target=reader, args=(i,)) for i in range(n_readers)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    assert not errors, errors
    assert all(c > 0 for c in checked), checked
    assert len(oracle) > 2           # readers really saw multiple versions
    # every pin released: refcounts at baseline, COW disarmed (close()
    # drops the service's own pooled standing pin)
    assert svc.freshness()["open_snapshots"] == 0
    svc.close()
    assert primary.snapshot_stats() == {"open_snapshots": 0,
                                        "pinned_epochs": 0}
    return sum(checked)


@pytest.mark.parametrize("mode", ["eager", "buffered"])
@pytest.mark.parametrize("n_shards", [None, 4])
def test_concurrent_readers_match_frozen_oracle(mode, n_shards):
    """Readers under live ingest serve byte-identical results to frozen
    deep copies at their snapshot's watermark — the full matrix."""
    run_concurrent_differential(mode, n_shards)


# ---------------------------------------------------------------------------
# thread-local planner state (satellite: shared last_plan fix)
# ---------------------------------------------------------------------------

def test_last_plan_is_thread_local():
    """Two interleaved planner queries on one engine: each thread must
    read back ITS plan, not the other thread's (last_plan used to be
    instance-shared state)."""
    batches, names = build_workload(300, seed=9)
    primary, ing, _ = make_service("eager", None, names, discovery=True)
    for b in batches:
        ing.ingest(b)
    q = QueryEngine(primary, AggregateIndex(), now=NOW, ingestor=ing)

    barrier = threading.Barrier(2, timeout=30)
    plans = {}
    errors = []

    def worker(tid, fn):
        try:
            barrier.wait()           # both run their query...
            fn()
            barrier.wait()           # ...then both read last_plan back
            plans[tid] = q.last_plan
        except BaseException as e:
            errors.append(repr(e))

    ts = [threading.Thread(target=worker,
                           args=(0, lambda: q.find_by_name(r"f1\d$"))),
          threading.Thread(target=worker,
                           args=(1, lambda: q.world_writable()))]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=60)
    assert not errors, errors
    assert plans[0]["query"] == "find_by_name"
    assert plans[1]["query"] == "world_writable"
    # the main thread never planned anything: its slot is untouched
    assert q.last_plan is None


# ---------------------------------------------------------------------------
# result cache semantics (satellite: accounting + invalidation)
# ---------------------------------------------------------------------------

def test_cache_hit_miss_accounting():
    batches, names = build_workload(200, seed=3)
    primary, ing, svc = make_service("eager", None, names)
    for b in batches:
        ing.ingest(b)

    r1 = svc.query("find_by_glob", "/fs/*")
    assert r1["freshness"]["cached"] is False
    r2 = svc.query("find_by_glob", "/fs/*")
    assert r2["freshness"]["cached"] is True
    assert_same_result(r2["result"], r1["result"])
    # different params = different key
    r3 = svc.query("find_by_glob", "/fs/d*")
    assert r3["freshness"]["cached"] is False
    st = svc.cache.stats
    assert st["hits"] == 1 and st["misses"] == 2
    assert svc.cache.hit_rate() == pytest.approx(1 / 3)


def test_cache_invalidates_on_mutation_not_on_noop_batch():
    """The cache drops exactly on MUTATING watermark advance: an
    all-OPEN batch (coalesced away entirely) advances the raw ingest
    watermark but not the data version — cached results stay live."""
    batches, names = build_workload(200, seed=3)
    primary, ing, svc = make_service("eager", None, names)
    for b in batches:
        ing.ingest(b)

    r1 = svc.query("world_writable")
    wm1 = r1["freshness"]["watermark"]
    raw1 = ing.freshness()["applied_seq"]
    inv0 = svc.cache.stats["invalidations"]

    # a pure-OPEN batch: filter_opens drops every event, so the
    # coalescer yields no facts — the apply is a watermark-only no-op
    stream = ev.EventStream(start_fid=100000)
    fid = 1            # any known fid: OPEN events don't touch state
    for _ in range(10):
        stream.emit(ev.E_OPEN, fid)
    noop = stream.take(64)
    noop["seq"] = noop["seq"] + raw1     # seqs beyond the applied head
    ing.ingest(noop)
    live = primary.live_paths()

    assert ing.freshness()["applied_seq"] > raw1       # watermark moved
    r2 = svc.query("world_writable")
    assert r2["freshness"]["cached"] is True           # cache survived
    assert r2["freshness"]["watermark"] == wm1         # same data version
    assert svc.cache.stats["invalidations"] == inv0    # no drop fired

    # a real mutation invalidates: same query recomputes at a new token
    ing.ingest(batches[0])
    r3 = svc.query("world_writable")
    assert r3["freshness"]["cached"] is False
    assert r3["freshness"]["watermark"] > wm1
    assert svc.cache.stats["invalidations"] > inv0
    assert svc.cache.stats["entries_dropped"] >= 1
    assert len(live) > 0


def test_singleflight_coalesces_concurrent_misses(monkeypatch):
    """N readers missing the SAME key at the same watermark do one
    underlying scan between them: the first becomes the computer, the
    rest wait on its in-flight event and read the fill — an
    invalidation storm costs one scan per distinct query, not one per
    reader."""
    batches, names = build_workload(200, seed=3)
    primary, ing, svc = make_service("eager", None, names)
    for b in batches:
        ing.ingest(b)

    calls = []
    gate = threading.Barrier(4, timeout=10)
    real = QueryEngine.world_writable

    def slow(self, *a, **kw):
        calls.append(threading.get_ident())
        time.sleep(0.05)        # hold the flight open so misses pile up
        return real(self, *a, **kw)

    monkeypatch.setattr(QueryEngine, "world_writable", slow)
    results, errors = [], []

    def go():
        try:
            gate.wait()
            results.append(svc.query("world_writable"))
        except BaseException as e:              # pragma: no cover
            errors.append(repr(e))

    threads = [threading.Thread(target=go) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    assert not errors, errors
    assert len(calls) == 1                      # ONE scan, four answers
    assert len(results) == 4
    assert sum(1 for r in results
               if r["freshness"]["cached"] is False) == 1
    for r in results[1:]:
        assert_same_result(r["result"], results[0]["result"])
    assert not svc._inflight                    # table drained


def test_cache_lru_eviction_bound():
    cache = ResultCache(capacity=4)
    for i in range(10):
        cache.put(("q", i), i)
    assert len(cache) == 4
    assert cache.stats["evicted"] == 6
    assert cache.get(("q", 9)) == 9
    assert cache.get(("q", 0)) is ResultCache._MISS


def test_out_of_band_mutation_self_heals():
    """A writer that bypasses the ingestor (no on_apply hook) is caught
    by the mutation-epoch probe at snapshot time: the stale entry is
    dropped, never served."""
    batches, names = build_workload(200, seed=3)
    primary, ing, svc = make_service("eager", None, names)
    for b in batches:
        ing.ingest(b)
    r1 = svc.query("find_by_glob", "/fs/*")
    wm1 = r1["freshness"]["watermark"]
    primary.upsert("/fs/oob", {"size": 1.0, "mtime": 1.0}, version=10**9)
    r2 = svc.query("find_by_glob", "/fs/*")
    assert r2["freshness"]["cached"] is False
    assert r2["freshness"]["watermark"] > wm1
    assert len(r2["result"]) == len(r1["result"]) + 1


def test_snapshot_pins_release_to_baseline():
    """Leak check: open snapshots and cursors, close them all, and the
    arena refcounts are back at baseline — with COW disarmed (mutations
    stop copying once nothing is pinned)."""
    batches, names = build_workload(300, seed=13)
    for n_shards in (None, 4):
        primary, ing, svc = make_service("eager", n_shards, names)
        for b in batches:
            ing.ingest(b)
        snaps = [svc.snapshot() for _ in range(5)]
        pg = svc.query_page("find_by_glob", "/fs/*", page_size=7)
        assert svc.freshness()["open_snapshots"] == 6
        assert primary.snapshot_stats()["open_snapshots"] == \
            6 * (n_shards or 1)
        ing.ingest(batches[0])       # churn while pinned
        for s in snaps:
            s.close()
            s.close()                # idempotent
        assert svc.close_cursor(pg["cursor"])
        assert not svc.close_cursor(pg["cursor"])
        assert svc.freshness()["open_snapshots"] == 0
        assert svc.freshness()["open_cursors"] == 0
        assert primary.snapshot_stats() == {"open_snapshots": 0,
                                            "pinned_epochs": 0}
        # COW disarmed: the next mutation must not copy arenas
        shard = primary.shards[0] if n_shards else primary
        assert not shard._shared
        ing.ingest(batches[1])
        assert not shard._shared


# ---------------------------------------------------------------------------
# cursor stability across ingest
# ---------------------------------------------------------------------------

def test_cursor_pages_stable_under_ingest():
    """Pages fetched while ingest advances between them come from the
    cursor's pinned snapshot: concatenated pages equal the full frozen
    result — no skipped rows, no duplicates, no rows from the future."""
    batches, names = build_workload(600, seed=21)
    primary, ing, svc = make_service("eager", 4, names)
    half = len(batches) // 2
    for b in batches[:half]:
        ing.ingest(b)

    with primary.write_lock():
        frozen = index_from_state(primary.state_dict())
    want = QueryEngine(frozen, AggregateIndex(), now=NOW) \
        .find_by_glob("/fs/*")

    pg = svc.query_page("find_by_glob", "/fs/*", page_size=5)
    wm0 = pg["watermark"]
    rows = list(pg["rows"])
    tok = pg["cursor"]
    for b in batches[half:]:         # churn between every page fetch
        ing.ingest(b)
        if tok is not None:
            pg = svc.query_page(cursor=tok)
            assert pg["watermark"] == wm0
            rows += list(pg["rows"])
            tok = pg["cursor"]
    while tok is not None:
        pg = svc.query_page(cursor=tok)
        rows += list(pg["rows"])
        tok = pg["cursor"]
    assert np.array_equal(np.asarray(rows, object), want)
    # the same query NOW sees the post-ingest world instead
    now_rows = svc.query("find_by_glob", "/fs/*")
    assert now_rows["freshness"]["watermark"] > wm0
    assert len(now_rows["result"]) != len(want) or \
        not np.array_equal(now_rows["result"], want)


def test_cursor_token_validation():
    batches, names = build_workload(200, seed=2)
    primary, ing, svc = make_service("eager", None, names)
    for b in batches:
        ing.ingest(b)
    pg = svc.query_page("find_by_glob", "/fs/*", page_size=3)
    bad = dict(pg["cursor"], watermark=pg["cursor"]["watermark"] + 1)
    with pytest.raises(ValueError):
        svc.query_page(cursor=bad)
    svc.close_cursor(pg["cursor"])
    with pytest.raises(KeyError):
        svc.query_page(cursor=pg["cursor"])
    with pytest.raises(ValueError):
        svc.query_page()             # neither name nor cursor
    with pytest.raises(ValueError):
        svc.query("no_such_query")


# ---------------------------------------------------------------------------
# monitor export of serving-tier freshness
# ---------------------------------------------------------------------------

def test_monitor_exports_served_freshness():
    from repro.core.monitor import Monitor, MonitorConfig

    batches, names = build_workload(200, seed=4)
    primary, ing, svc = make_service("eager", None, names)
    stream = ev.EventStream(start_fid=1)
    td.gen_workload(stream, 120, seed=4)
    mon = Monitor(MonitorConfig(batch_size=64, max_fids=1 << 12),
                  ingestor=ing, query_service=svc)
    svc.query("world_writable")
    svc.query("world_writable")
    pinned = svc.snapshot()          # something served trails the head
    ing.ingest(batches[0])
    out = mon.run(stream)
    assert out["served_watermark"] == svc.data_version
    assert out["open_snapshots"] == 1
    assert out["snapshot_lag"] > 0
    assert 0.0 < out["cache_hit_rate"] <= 1.0
    pinned.close()
    assert mon.run(ev.EventStream(start_fid=10**6))["snapshot_lag"] == 0


# ---------------------------------------------------------------------------
# ISSUE 7: time-relative cache keys + batched dashboard execution
# ---------------------------------------------------------------------------

def _one_file_service(**kw):
    idx = PrimaryIndex()
    idx.upsert_batch(
        ["/fs/a"], {"path_hash": np.array([1], np.uint32),
                    "atime": np.array([999.0], np.float32),
                    "mtime": np.array([999.0], np.float32)},
        np.array([1], np.int64))
    t = {"now": 1400.0}
    svc = QueryService(idx, AggregateIndex(), now=lambda: t["now"], **kw)
    return idx, t, svc


def test_time_relative_cache_follows_clock_without_ingest():
    """ISSUE 7 regression: a file crosses the idle cutoff purely by the
    clock advancing — ZERO ingest between the two queries, so the
    watermark never moves. The old ``(name, args, kw, watermark)`` key
    served the frozen first answer forever at an idle index."""
    _, t, svc = _one_file_service()
    r1 = svc.query("not_accessed_since", 500.0)
    assert list(r1["result"]) == []          # cutoff 900 < atime 999
    t["now"] = 1600.0                        # cutoff 1100 > atime 999
    r2 = svc.query("not_accessed_since", 500.0)
    assert r2["freshness"]["cached"] is False
    assert list(r2["result"]) == ["/fs/a"]
    # the other two time-relative queries key the same way
    t["now"] = 1400.0
    assert list(svc.query("past_retention", 500.0)["result"]) == []
    t["now"] = 1600.0
    assert list(svc.query("past_retention", 500.0)["result"]) == ["/fs/a"]


def test_time_relative_cache_coalesces_within_bucket():
    """Inside one freshness bucket the clock component of the key is
    identical — hits still coalesce; a non-time query's key has no
    clock component at all and survives any clock advance."""
    _, t, svc = _one_file_service(now_bucket_s=10.0)
    assert svc.query("not_accessed_since", 500.0)[
        "freshness"]["cached"] is False
    t["now"] = 1404.0                        # same 10s bucket
    assert svc.query("not_accessed_since", 500.0)[
        "freshness"]["cached"] is True
    t["now"] = 1411.0                        # next bucket -> recompute
    assert svc.query("not_accessed_since", 500.0)[
        "freshness"]["cached"] is False
    t["now"] = 1400.0
    assert svc.query("find_by_glob", "/fs/*")[
        "freshness"]["cached"] is False
    t["now"] = 9999.0                        # clock-independent query
    assert svc.query("find_by_glob", "/fs/*")[
        "freshness"]["cached"] is True


def test_time_relative_bucket_zero_disables_coalescing():
    """Bucket <= 0 keys on the RAW clock: identical reads (a pinned
    test clock) still hit, but any tick at all misses — no wall-clock
    staleness window whatsoever."""
    _, t, svc = _one_file_service(now_bucket_s=0.0)
    assert svc.query("past_retention", 500.0)[
        "freshness"]["cached"] is False
    assert svc.query("past_retention", 500.0)[
        "freshness"]["cached"] is True       # clock frozen -> same key
    t["now"] += 1e-6                         # any tick -> miss
    assert svc.query("past_retention", 500.0)[
        "freshness"]["cached"] is False


BATCH = [
    ("world_writable",),
    ("not_accessed_since", 1.5e6),
    ("large_cold_files", 1e4, 1.7e6),
    ("owned_by_deleted_users", [0, 1, 2, 3]),
    ("past_retention", 1.3e6),
    ("find_by_glob", "/fs/*f*1*"),
    ("duplicate_candidates",),
    {"name": "not_accessed_since", "args": (1.5e6,)},     # duplicate
]


@pytest.mark.parametrize("n_shards", [None, 4])
def test_query_batch_matches_single_queries(n_shards):
    """§13.4: one pooled snapshot + one clock for the whole dashboard
    mix; every result byte-identical to the single-query path, cache
    shared both ways, duplicates computed once."""
    batches, names = build_workload(300, seed=11)
    primary, ing, svc = make_service("eager", n_shards, names)
    _, ing2, ref = make_service("eager", n_shards, names)
    for b in batches:
        ing.ingest(b)
        ing2.ingest(b)

    got = svc.query_batch(BATCH)
    assert len(got) == len(BATCH)
    assert svc.stats["batches"] == 1
    for r, req in zip(got, BATCH):
        name, args = (req["name"], req["args"]) if isinstance(req, dict) \
            else (req[0], req[1:])
        want = ref.query(name, *args)
        assert_same_result(r["result"], want["result"], name)
        assert r["freshness"]["watermark"] == want["freshness"]["watermark"]
    # the duplicate request hit the first occurrence's entry
    assert got[-1]["freshness"]["cached"] is True
    assert_same_result(got[-1]["result"], got[1]["result"])
    # a second identical batch is all cache hits...
    again = svc.query_batch(BATCH)
    assert all(r["freshness"]["cached"] for r in again)
    # ...and single-query traffic shares the same entries
    assert svc.query("world_writable")["freshness"]["cached"] is True


def test_query_batch_rejects_unknown_query():
    _, _, svc = _one_file_service()
    with pytest.raises(ValueError, match="unknown query"):
        svc.query_batch([("world_writable",), ("drop_tables",)])


def test_query_batch_time_relative_uses_one_clock():
    """All time-relative members of one batch resolve the same now —
    and that now keys their cache entries, so a later batch after a
    clock advance recomputes instead of serving the old cutoff."""
    _, t, svc = _one_file_service()
    r = svc.query_batch([("not_accessed_since", 500.0),
                         ("past_retention", 500.0)])
    assert [list(x["result"]) for x in r] == [[], []]
    t["now"] = 1600.0
    r = svc.query_batch([("not_accessed_since", 500.0),
                         ("past_retention", 500.0)])
    assert [list(x["result"]) for x in r] == [["/fs/a"], ["/fs/a"]]
    assert not any(x["freshness"]["cached"] for x in r)


# ---------------------------------------------------------------------------
# readers through the service while out-of-band churn lands
# ---------------------------------------------------------------------------

N_CHURN = 5


@pytest.fixture(scope="module")
def churned_service():
    """Four reader threads run a dashboard mix (16 cache keys) through a
    ``QueryService`` over a 4-shard index with discovery attached, while
    a writer applies ``N_CHURN`` versioned ``upsert_batch`` rewrites
    directly (the out-of-band path, caught by the epoch probe). Readers
    keep going until the writer is done, then make two more passes.
    Returns the observations the tests below read."""
    files = files_only(synth_filesystem(4000, seed=9))
    idx = ShardedPrimaryIndex(4)
    idx.ingest_table(files, 1)
    idx.attach_discovery()
    svc = QueryService(idx, AggregateIndex(), now=1.7e9, max_readers=4)
    probes = list(files.paths[:4])
    mix = []
    for v in range(4):
        mix += [("find_by_glob", f"*/f{31 + v}??"),
                ("stat", probes[v]),
                ("not_accessed_since", (180 + 60 * v) * 86400),
                ("owned_by_deleted_users", list(range(20 + 2 * v)))]
    version_before = svc.freshness()["served_watermark"]
    rng = np.random.default_rng(7)
    churn = []
    for i in range(N_CHURN):
        pick = rng.choice(len(files), size=400, replace=False)
        churn.append((list(files.paths[pick]),
                      {"path_hash": files.path_hash[pick],
                       "size": files.size[pick].astype(np.float32) + i,
                       "atime": files.atime[pick].astype(np.float32)},
                      np.full(len(pick), 2 + i, np.int64)))
    done = threading.Event()
    errors = []

    def reader(rid):
        try:
            extra = 2
            while extra:
                if done.is_set():
                    extra -= 1
                for m in range(len(mix)):
                    name, *args = mix[(m + rid) % len(mix)]
                    svc.query(name, *args)
        except BaseException as e:             # pragma: no cover
            errors.append(repr(e))

    def writer():
        for paths, fields, vers in churn:
            idx.upsert_batch(paths, fields, vers)
            time.sleep(0.01)
        done.set()

    threads = [threading.Thread(target=reader, args=(i,)) for i in range(4)]
    threads.append(threading.Thread(target=writer))
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=600)
    assert not errors, errors
    fr = svc.freshness()
    svc.close()
    return {"freshness": fr, "version_before": version_before,
            "pins_after_close": idx.snapshot_stats()}


def test_churn_leaks_no_snapshot(churned_service):
    assert churned_service["freshness"]["open_snapshots"] == 0
    assert churned_service["pins_after_close"] == {"open_snapshots": 0,
                                                   "pinned_epochs": 0}


def test_churn_hit_rate_strictly_between_zero_and_one(churned_service):
    """All hits would mean the churn never invalidated; all misses, that
    the cache never served."""
    cache = churned_service["freshness"]["cache"]
    assert 0.0 < cache["hit_rate"] < 1.0, cache
    assert cache["invalidations"] >= 1, cache


def test_churn_advances_data_version(churned_service):
    """The out-of-band writes advance the served data version, one step
    per change the epoch probe caught (batches landing between two
    snapshots count once), each step with its cache invalidation."""
    fr = churned_service["freshness"]
    steps = fr["served_watermark"] - churned_service["version_before"]
    assert 1 <= steps <= N_CHURN
    assert steps == fr["cache"]["invalidations"]
