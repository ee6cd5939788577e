"""Discovery-index suite (ISSUE 5): planner equivalence, incremental
maintenance, staleness/fallback/rebuild, freshness threading, and the
checkpoint/restore leg.

The load-bearing property is **byte-identity**: every accelerated query
(sorted-run/zone-map range + set predicates, trigram-prefiltered
substring/glob name search) must return exactly what the scan path
returns — same subset, same order, same dtypes — across random corpora,
delta-buffer fill levels, merge/rebuild boundaries, staleness states,
and 1/4 shards. The hypothesis leg sweeps that matrix; the crash leg
pins that discovery state after checkpoint/restore + suffix replay
matches the uninterrupted oracle's observable state.
"""
import functools

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import discovery as disc
from repro.core import events as ev
from repro.core import snapshot as snap
from repro.core.discovery import (DiscoveryConfig, glob_literals,
                                  index_lag, literal_trigrams,
                                  regex_literals, trigram_codes)
from repro.core.event_ingest import EventIngestor, IngestConfig
from repro.core.eventlog import EventLog
from repro.core.index import AggregateIndex, PrimaryIndex
from repro.core.metadata import files_only, synth_filesystem
from repro.core.query import QueryEngine, merge_freshness
from repro.core.reconcile import compact_if_needed
from repro.core.sharded_index import ShardedPrimaryIndex
from repro.core.stream_pipeline import DurablePipeline
from test_differential import gen_workload

PCFG = snap.PipelineConfig(n_users=8, n_groups=4, n_dirs=16)
NOW = 1.7e9

LAYOUTS = {"mono": lambda: PrimaryIndex(),
           "sharded1": lambda: ShardedPrimaryIndex(1),
           "sharded4": lambda: ShardedPrimaryIndex(4)}


def make_pair(n_files=4000, seed=0, layout="mono", cfg=None):
    """(accelerated engine, scan-oracle engine) over the same corpus —
    the oracle primary has no discovery index attached, so it can only
    scan. Both engines pin ``use_kernels=False``: this suite isolates
    the discovery-vs-scan equivalence (the fused predicate kernel has
    its own differential suite, tests/test_predeval.py, and would
    otherwise absorb the stale-fallback route assertions)."""
    fs = files_only(synth_filesystem(n_files, seed=seed))
    fast, oracle = LAYOUTS[layout](), LAYOUTS[layout]()
    fast.ingest_table(fs, 1)
    oracle.ingest_table(fs, 1)
    fast.attach_discovery(cfg)
    return (QueryEngine(fast, AggregateIndex(), now=NOW,
                        use_kernels=False),
            QueryEngine(oracle, AggregateIndex(), now=NOW,
                        use_kernels=False), fs)


QUERIES = [
    ("world_writable", lambda q: q.world_writable()),
    ("not_accessed_since", lambda q: q.not_accessed_since(180 * 86400)),
    ("large_cold_files", lambda q: q.large_cold_files(1e6, 90 * 86400)),
    ("owned_by_deleted_users",
     lambda q: q.owned_by_deleted_users(list(range(8)))),
    ("past_retention", lambda q: q.past_retention(365 * 86400)),
    ("find_by_name", lambda q: q.find_by_name(r"/f12\d$")),
    ("find_by_glob", lambda q: q.find_by_glob("*/f1?3")),
]


def assert_equiv(q, oracle, expect_route=None, ctx=""):
    """Every plannable query byte-identical between the two engines."""
    for name, fn in QUERIES:
        a, b = fn(q), fn(oracle)
        assert a.dtype == b.dtype, (ctx, name)
        assert np.array_equal(a, b), (ctx, name, len(a), len(b))
        if expect_route is not None:
            assert q.last_plan["route"] == expect_route, \
                (ctx, name, q.last_plan)


# ---------------------------------------------------------------------------
# literal extraction + trigram building blocks
# ---------------------------------------------------------------------------

def test_regex_literals():
    assert regex_literals(r"/f12\d$") == ["/f12"]
    assert regex_literals(r"^/fs/data/file\.h5$") == ["/fs/data/file.h5"]
    assert regex_literals(r"(checkpoint)_v\d+") == ["checkpoint", "_v"]
    assert regex_literals(r"ab+core") == ["a", "b", "core"]  # b occurs >=1
    # no guaranteed literal: alternation, optional, char class, flags
    assert regex_literals(r"foo|bar") == []
    assert regex_literals(r"(core)?dump") == ["dump"]
    assert regex_literals(r"(?i)core") == []              # case games: scan
    assert regex_literals(r"[abc]+") == []
    assert regex_literals(r"(") == []                     # unparsable: scan


def test_glob_literals_and_trigrams():
    assert glob_literals("*/scratch/f?123") == ["/scratch/f", "123"]
    assert glob_literals("???") == []
    # a [...] class matches ONE char: its contents are NOT a literal
    # run (treating "abc" as required here silently dropped matches)
    assert glob_literals("*[abc]*") == []
    assert glob_literals("f[0-9]oo*") == ["f", "oo"]
    assert glob_literals("*[!abc]x") == ["x"]
    assert glob_literals("*[]]end") == ["end"]            # ']' first: literal
    assert glob_literals("data[broken") == ["data"]       # unterminated: safe
    assert literal_trigrams(["abcd"]) == sorted(
        {(ord("a") << 16) | (ord("b") << 8) | ord("c"),
         (ord("b") << 16) | (ord("c") << 8) | ord("d")})
    assert literal_trigrams(["ab", "x"]) == []            # nothing >= 3 bytes


def test_glob_bracket_class_byte_identity():
    """Regression: the discovery route for a bracketed glob must match
    the scan exactly (bracket contents used to leak in as a required
    literal and silently drop matches)."""
    q, oracle, _ = make_pair(600, seed=12)
    for pat in ("*[spq]*", "*/f[0-9][0-9]", "*/d1/f*[02468]"):
        a, b = q.find_by_glob(pat), oracle.find_by_glob(pat)
        assert np.array_equal(a, b), (pat, len(a), len(b))
        assert len(b) > 0, pat                 # the pattern really matches


def test_trigram_vectorized_matches_host_loop():
    paths = np.array(["/fs/d1/f1", "/fs/d2/longer_name.dat", "/a",
                      "/fs/d1/f1"], object)
    slots = np.arange(4, dtype=np.int64)
    codes, ss = disc._trigram_pairs(paths, slots, chunk_windows=8)
    want_c, want_s = [], []
    for p, s in zip(paths, slots):
        cs = trigram_codes(p.encode())
        want_c += cs
        want_s += [s] * len(cs)
    order = np.lexsort((ss, codes))
    worder = np.lexsort((want_s, np.asarray(want_c)))
    assert np.array_equal(codes[order], np.asarray(want_c, np.int32)[worder])
    assert np.array_equal(ss[order], np.asarray(want_s, np.int64)[worder])


def test_trigram_non_ascii_fallback():
    paths = np.array(["/fs/données/f1", "/fs/d2/f2"], object)
    codes, ss = disc._trigram_pairs(paths, np.arange(2, dtype=np.int64),
                                    chunk_windows=1024)
    assert len(codes) == sum(len(p.encode("utf-8")) - 2 for p in paths)


def test_zone_map_prunes_runs():
    idx = PrimaryIndex()
    fs = files_only(synth_filesystem(500, seed=1))
    idx.ingest_table(fs, 1)
    d = idx.attach_discovery()
    run = d.runs[0]
    lo, hi = run.zone["size"]
    # a range entirely above the zone max returns the empty slice
    assert len(run.candidates("size", "gt", float(hi) * 2 + 1)) == 0
    assert len(run.candidates("size", "lt", float(lo) / 2)) == 0
    # and a covering range returns every covered slot
    assert len(run.candidates("size", "gt", -1.0)) == run.n


# ---------------------------------------------------------------------------
# planner equivalence: bulk, incremental, staleness, shards
# ---------------------------------------------------------------------------

#: selective variants of the Table-I queries: the regime the discovery
#: index serves (interactive "find my files", policy candidate lists)
SELECTIVE = {
    "find_by_name": lambda q: q.find_by_name(r"/f123\d$"),
    "find_by_glob": lambda q: q.find_by_glob("*/f39?9"),
    "not_accessed_since": lambda q: q.not_accessed_since(365 * 86400),
    "large_cold_files": lambda q: q.large_cold_files(1e7, 180 * 86400),
    "past_retention": lambda q: q.past_retention(2 * 365 * 86400),
    # all but the 4 rarest owners active: a deleted-user cleanup sweep
    "owned_by_deleted_users":
        lambda q: q.owned_by_deleted_users(list(range(28))),
}


@functools.lru_cache(maxsize=None)
def bulk_pair(layout):
    """One read-only (accelerated, oracle) pair per layout, shared by
    the bulk cases."""
    return make_pair(layout=layout, seed=2)


@pytest.mark.parametrize("layout,query", [
    *(pytest.param(layout, None, id=layout) for layout in sorted(LAYOUTS)),
    *(pytest.param(layout, query, id=f"{layout}-{query}")
      for layout in sorted(LAYOUTS) for query in SELECTIVE)])
def test_bulk_equivalence(layout, query):
    """The Table-I suite (``query`` None), then each query at its
    selective parameters, routes through discovery and byte-matches the
    scan."""
    q, oracle, _ = bulk_pair(layout)
    if query is None:
        assert_equiv(q, oracle, expect_route="discovery", ctx=layout)
        return
    a, b = SELECTIVE[query](q), SELECTIVE[query](oracle)
    assert q.last_plan["route"] == "discovery", (layout, q.last_plan)
    assert a.dtype == b.dtype, (layout, query)
    assert np.array_equal(a, b), (layout, query, len(a), len(b))


@pytest.mark.parametrize("layout", ["mono", "sharded4"])
def test_incremental_equivalence_across_merge_boundaries(layout):
    """Upsert/delete churn with a tiny merge threshold: results stay
    byte-identical while the delta buffer fills, folds into runs, and
    overflows max_runs into a full rebuild."""
    cfg = DiscoveryConfig(merge_threshold=64, max_runs=3)
    q, oracle, fs = make_pair(2000, seed=3, layout=layout, cfg=cfg)
    rng = np.random.default_rng(0)
    ver = 2
    for step in range(8):
        # mutate BOTH sides identically through the batch protocol
        pick = rng.choice(len(fs.paths), size=40, replace=False)
        paths = list(fs.paths[pick])
        fields = {
            "path_hash": fs.path_hash[pick],
            "uid": rng.integers(0, 16, 40).astype(np.int32),
            "size": rng.gamma(1.5, 1e5, 40).astype(np.float32),
            "atime": (NOW - rng.exponential(200 * 86400, 40)
                      ).astype(np.float32),
            "mtime": (NOW - rng.exponential(400 * 86400, 40)
                      ).astype(np.float32),
            "mode": rng.choice([0o644, 0o666, 0o600], 40).astype(np.int32),
        }
        dead = list(rng.choice(fs.paths, size=15, replace=False))
        for primary in (q.primary, oracle.primary):
            primary.upsert_batch(paths, fields,
                                 np.full(40, ver, np.int64))
            primary.delete_batch(dead, np.full(15, ver + 1, np.int64))
        ver += 2
        assert_equiv(q, oracle, expect_route="discovery",
                     ctx=f"{layout} step={step}")
    ds = disc.discovery_shards(q.primary)
    stats = [d.stats for d in ds]
    assert sum(s["merges"] for s in stats) > 0      # deltas really folded
    assert all(d.fresh for d in ds)


@pytest.mark.parametrize("layout", ["mono", "sharded4"])
def test_stale_fallback_rebuild_cycle(layout):
    """fresh -> (snapshot re-ingest) stale -> scan fallback -> rebuild
    -> accelerated again; index_lag tracks the cycle."""
    q, oracle, fs = make_pair(1500, seed=4, layout=layout)
    assert index_lag(q.primary) == 0
    q.find_by_name(r"/f12\d$")
    assert q.last_plan["route"] == "discovery"
    # bulk snapshot ingest cannot be absorbed slot-by-slot
    q.primary.ingest_table(fs, 5)
    oracle.primary.ingest_table(fs, 5)
    assert index_lag(q.primary) > 0
    assert_equiv(q, oracle, expect_route="scan", ctx="stale")
    # rebuild re-arms acceleration
    q.primary.rebuild_discovery()
    assert index_lag(q.primary) == 0
    assert_equiv(q, oracle, expect_route="discovery", ctx="rebuilt")


def test_index_lag_counts_mutations_while_stale():
    """Regression: index_lag must keep counting mutations behind a
    stale index (it used to pin at 1 because the sync mark advanced
    even while stale) — operators see how far discovery has drifted."""
    q, _, fs = make_pair(300, seed=14)
    q.primary.ingest_table(fs, 2)            # invalidate (1 mutation)
    assert index_lag(q.primary) == 1
    for i in range(5):
        q.primary.delete_batch([fs.paths[i]], np.array([3 + i]))
    assert index_lag(q.primary) == 6
    q.primary.rebuild_discovery()
    assert index_lag(q.primary) == 0


def test_load_state_invalidates_discovery():
    q, _, _ = make_pair(300, seed=5)
    state = q.primary.state_dict()
    q.primary.load_state(state)
    assert index_lag(q.primary) > 0
    q.world_writable()
    assert q.last_plan["route"] == "scan"


@pytest.mark.parametrize("layout", ["mono", "sharded4"])
def test_compaction_rebuilds_discovery(layout):
    """Compaction renumbers slots: the attached discovery index must be
    rebuilt from live rows in the same call, staying fresh and exact."""
    q, oracle, fs = make_pair(1200, seed=6, layout=layout)
    doomed = list(fs.paths[: len(fs.paths) // 2])
    vers = np.full(len(doomed), 3, np.int64)
    q.primary.delete_batch(doomed, vers)
    oracle.primary.delete_batch(doomed, vers)
    assert compact_if_needed(q.primary, threshold=0.1) > 0
    compact_if_needed(oracle.primary, threshold=0.1)
    assert index_lag(q.primary) == 0
    assert_equiv(q, oracle, expect_route="discovery", ctx="compacted")


def test_event_feed_keeps_discovery_fresh():
    """An event-ingestor-driven index (creates, stat updates, deletes,
    dir renames — the version-gated apply path) publishes every touched
    slot; accelerated queries stay byte-identical throughout."""
    stream = ev.EventStream(start_fid=1)
    gen_workload(stream, 300, seed=9)
    names = {0: "fs", **stream.names}
    batches = []
    while len(stream):
        batches.append(stream.take(64))
    engines = []
    for accel in (True, False):
        primary = ShardedPrimaryIndex(3)
        if accel:
            primary.attach_discovery(DiscoveryConfig(merge_threshold=128))
        ing = EventIngestor(
            IngestConfig(mode="eager", pad_to=64, update_aggregates=False),
            PCFG, primary, AggregateIndex(), names=names)
        for b in batches:
            ing.ingest(b)
        engines.append(QueryEngine(primary, AggregateIndex(), now=NOW,
                                   ingestor=ing))
    q, oracle = engines
    assert q.ingestor.freshness()["index_lag"] == 0
    assert_equiv(q, oracle, ctx="event-fed")
    assert q.last_plan["route"] == "discovery"


def test_idempotent_replay_preserves_discovery_exactness():
    """Replaying an already-applied suffix (every row version-gated to
    a no-op) must not corrupt discovery answers."""
    stream = ev.EventStream(start_fid=1)
    gen_workload(stream, 200, seed=10)
    names = {0: "fs", **stream.names}
    batches = []
    while len(stream):
        batches.append(stream.take(64))
    primary = PrimaryIndex()
    primary.attach_discovery(DiscoveryConfig(merge_threshold=64))
    ing = EventIngestor(
        IngestConfig(mode="eager", pad_to=64, update_aggregates=False),
        PCFG, primary, AggregateIndex(), names=names)
    for b in batches:
        ing.ingest(b)
    q = QueryEngine(primary, AggregateIndex(), now=NOW)
    before = {n: fn(q).tolist() for n, fn in QUERIES}
    for b in batches[len(batches) // 2:]:       # replay a stale suffix
        ing.ingest(b)
    assert index_lag(primary) == 0
    after = {n: fn(q).tolist() for n, fn in QUERIES}
    assert before == after
    assert q.last_plan["route"] == "discovery"


# ---------------------------------------------------------------------------
# property test: the full matrix under randomized operation sequences
# ---------------------------------------------------------------------------

@settings(max_examples=12, deadline=None)
@given(seed=st.integers(0, 10_000),
       n_files=st.integers(200, 1500),
       n_shards=st.sampled_from([1, 4]),
       merge_threshold=st.sampled_from([16, 256, 100_000]),
       n_ops=st.integers(1, 6))
def test_property_planner_equivalence(seed, n_files, n_shards,
                                      merge_threshold, n_ops):
    """Random corpora x random mutation sequences (batch upserts,
    deletes, occasional snapshot re-ingest = staleness, rebuilds) x
    delta fill levels x shard counts: accelerated and scan answers are
    byte-identical after every operation."""
    rng = np.random.default_rng(seed)
    fs = files_only(synth_filesystem(n_files, seed=seed % 17))
    cfg = DiscoveryConfig(merge_threshold=merge_threshold, max_runs=2)
    fast, oracle = ShardedPrimaryIndex(n_shards), ShardedPrimaryIndex(n_shards)
    fast.ingest_table(fs, 1)
    oracle.ingest_table(fs, 1)
    fast.attach_discovery(cfg)
    q = QueryEngine(fast, AggregateIndex(), now=NOW)
    qo = QueryEngine(oracle, AggregateIndex(), now=NOW)
    ver = 2
    for _ in range(n_ops):
        op = rng.choice(["upsert", "delete", "snapshot", "rebuild"],
                        p=[0.4, 0.3, 0.15, 0.15])
        if op == "upsert":
            k = int(rng.integers(1, 80))
            pick = rng.choice(len(fs.paths), size=k, replace=False)
            fields = {
                "path_hash": fs.path_hash[pick],
                "size": rng.gamma(1.5, 1e5, k).astype(np.float32),
                "atime": (NOW - rng.exponential(300 * 86400, k)
                          ).astype(np.float32),
                "mode": rng.choice([0o644, 0o666], k).astype(np.int32),
                "uid": rng.integers(0, 12, k).astype(np.int32),
            }
            vers = np.full(k, ver, np.int64)
            fast.upsert_batch(list(fs.paths[pick]), fields, vers)
            oracle.upsert_batch(list(fs.paths[pick]), fields, vers)
        elif op == "delete":
            k = int(rng.integers(1, 60))
            dead = list(rng.choice(fs.paths, size=k, replace=False))
            vers = np.full(k, ver, np.int64)
            fast.delete_batch(dead, vers)
            oracle.delete_batch(dead, vers)
        elif op == "snapshot":
            fast.ingest_table(fs, ver)
            oracle.ingest_table(fs, ver)
        else:
            fast.rebuild_discovery()
        ver += 1
        assert_equiv(q, qo, ctx=f"seed={seed} op={op}")


# ---------------------------------------------------------------------------
# freshness threading: ingestor -> merge_freshness -> monitor
# ---------------------------------------------------------------------------

def test_index_lag_threading():
    primary = PrimaryIndex()
    fs = files_only(synth_filesystem(400, seed=7))
    primary.ingest_table(fs, 1)
    ing = EventIngestor(IngestConfig(update_aggregates=False), PCFG,
                        primary, AggregateIndex())
    # stale (snapshot ingested after nothing attached -> attach leaves
    # it fresh; re-ingest makes it stale)
    primary.attach_discovery()
    assert ing.freshness()["index_lag"] == 0
    primary.ingest_table(fs, 2)
    lag = ing.freshness()["index_lag"]
    assert lag > 0
    merged = merge_freshness([ing.freshness(), ing.freshness()])
    assert merged["index_lag"] == 2 * lag
    # marks predating the discovery index default to 0
    old = {k: v for k, v in ing.freshness().items() if k != "index_lag"}
    assert merge_freshness([old])["index_lag"] == 0
    primary.rebuild_discovery()
    assert ing.freshness()["index_lag"] == 0


def test_monitor_surfaces_index_lag():
    from repro.core.monitor import Monitor, MonitorConfig
    primary = PrimaryIndex()
    primary.attach_discovery()
    ing = EventIngestor(IngestConfig(update_aggregates=False), PCFG,
                        primary, AggregateIndex())
    stream = ev.EventStream(start_fid=1)
    ev.filebench_workload(stream, 50, 20, seed=3)
    mon = Monitor(MonitorConfig(max_fids=1 << 12, batch_size=128),
                  ingestor=ing)
    out = mon.run(stream)
    assert out["index_lag"] == 0


# ---------------------------------------------------------------------------
# query() dispatch hardening (satellite)
# ---------------------------------------------------------------------------

def test_query_dispatch_allowlist():
    q, _, _ = make_pair(200, seed=8)
    got = q.query("find_by_name", r"/f1\d$")
    assert "result" in got and "freshness" in got
    for bad in ("now", "_plan_select", "primary", "freshness", "query",
                "__init__", "nonexistent"):
        with pytest.raises(ValueError, match="unknown query"):
            q.query(bad)


# ---------------------------------------------------------------------------
# checkpoint/restore: discovery state after recovery == oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_shards", [1, 4])
def test_crash_recovery_discovery_matches_oracle(tmp_path, n_shards):
    """Durable-pipeline leg: run a produce/pump/checkpoint schedule,
    kill the volatile half mid-stream, restore from the checkpoint
    (discovery rebuilds deterministically) and drain the suffix. The
    recovered engine's accelerated answers and freshness must match an
    uninterrupted oracle's, and both must route through discovery."""
    stream = ev.EventStream(start_fid=1)
    gen_workload(stream, 300, seed=21)
    names = {0: "fs", **stream.names}
    batches = []
    while len(stream):
        batches.append(stream.take(48))
    ckpt = str(tmp_path / "discovery.ckpt")

    def build(log):
        primary = ShardedPrimaryIndex(n_shards)
        primary.attach_discovery(DiscoveryConfig(merge_threshold=64))
        ing = EventIngestor(
            IngestConfig(mode="eager", pad_to=64, update_aggregates=False),
            PCFG, primary, AggregateIndex())
        return primary, ing, DurablePipeline(
            log, ing, n_partitions=2, batch_size=48)

    # uninterrupted oracle
    log = EventLog()
    o_primary, o_ing, o_pipe = build(log)
    for k, b in enumerate(batches):
        o_pipe.produce(b, names=names if k == 0 else None)
        if k % 2 == 0:
            o_pipe.pump()
    o_pipe.drain()

    # crashed run: checkpoint mid-stream, then lose the volatile half
    log = EventLog()
    primary, ing, pipe = build(log)
    cut = len(batches) // 2
    for k, b in enumerate(batches[:cut]):
        pipe.produce(b, names=names if k == 0 else None)
        if k % 2 == 0:
            pipe.pump()
    pipe.checkpoint(ckpt)
    for b in batches[cut:]:
        pipe.produce(b)
    # CRASH: only the log + checkpoint survive
    primary, ing, pipe = build(log)
    pipe.load_checkpoint(ckpt)
    assert index_lag(primary) == 0        # restore rebuilt discovery
    pipe.drain()

    q = QueryEngine(primary, AggregateIndex(), now=NOW, ingestor=ing)
    qo = QueryEngine(o_primary, AggregateIndex(), now=NOW, ingestor=o_ing)
    assert_equiv(q, qo, expect_route="discovery",
                 ctx=f"crash-recovery shards={n_shards}")
    assert q.freshness()["index_lag"] == 0
    assert (q.freshness()["applied_seq"]
            == qo.freshness()["applied_seq"])
