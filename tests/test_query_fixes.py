"""Query-layer correctness regressions (ISSUE 3 satellites).

- ``duplicate_candidates`` must GROUP BY the stand-in checksum column
  (``path_hash``), keyed by hash — grouping by ``size`` flooded the
  report with same-size/different-content files.
- ``QueryEngine.now`` must track a clock, not freeze at construction:
  a long-lived engine's cold-data / retention windows otherwise
  evaluate against a stale "now" forever.
"""
import time

import numpy as np

from repro.core.index import AggregateIndex, PrimaryIndex
from repro.core.metadata import path_hash, synth_filesystem
from repro.core.query import QueryEngine

# a real FNV-1a 32-bit collision (verified below): the stand-in
# "identical checksum" pair for the positive grouping case
COLLIDE_A = "/fs/d21/f398303"
COLLIDE_B = "/fs/d47/f485241"


def put(idx, paths, sizes, version=1, atime=None):
    n = len(paths)
    fields = {
        "path_hash": np.array([path_hash(p) for p in paths], np.uint32),
        "size": np.asarray(sizes, np.float32),
    }
    if atime is not None:
        fields["atime"] = np.asarray(atime, np.float32)
    idx.upsert_batch(list(paths), fields, np.full(n, version, np.int64))


def test_duplicate_candidates_groups_by_hash_not_size():
    """Same-size files with DIFFERENT hashes are not duplicates; files
    with the SAME hash are one group keyed by the hash — even when
    their sizes differ (a checksum match is the candidate signal, the
    size column is irrelevant to it)."""
    assert path_hash(COLLIDE_A) == path_hash(COLLIDE_B)   # pair is real
    idx = PrimaryIndex()
    # four same-size files, all distinct hashes: the old GROUP BY size
    # reported them all as one bogus duplicate group
    put(idx, [f"/fs/same/s{i}" for i in range(4)], [4096.0] * 4)
    q = QueryEngine(idx, AggregateIndex(), now=1.7e9)
    assert q.duplicate_candidates() == {}

    put(idx, [COLLIDE_A, COLLIDE_B], [111.0, 222.0])      # sizes differ
    dup = q.duplicate_candidates()
    assert set(dup) == {path_hash(COLLIDE_A)}
    assert sorted(dup[path_hash(COLLIDE_A)]) == [COLLIDE_A, COLLIDE_B]


def test_duplicate_candidates_excludes_tombstoned_rows():
    idx = PrimaryIndex()
    put(idx, [COLLIDE_A, COLLIDE_B], [1.0, 2.0])
    idx.delete_batch([COLLIDE_B], np.array([2]))
    q = QueryEngine(idx, AggregateIndex(), now=1.7e9)
    assert q.duplicate_candidates() == {}


def test_now_tracks_clock_in_long_lived_engine():
    """With a callable clock, the cold-data window moves as time does:
    the same engine returns different (correct) results later."""
    idx = PrimaryIndex()
    put(idx, ["/fs/hot", "/fs/cold"], [1.0, 1.0],
        atime=[1000.0, 100.0])
    t = {"now": 1050.0}
    q = QueryEngine(idx, AggregateIndex(), now=lambda: t["now"])
    assert q.now == 1050.0
    # at t=1050, only /fs/cold is idle > 500s
    assert sorted(q.not_accessed_since(500)) == ["/fs/cold"]
    assert sorted(q.large_cold_files(0.5, 500)) == ["/fs/cold"]
    t["now"] = 2000.0                 # both now idle > 500s
    assert sorted(q.not_accessed_since(500)) == ["/fs/cold", "/fs/hot"]
    assert sorted(q.past_retention(500)) == ["/fs/cold", "/fs/hot"]


def test_now_fixed_float_stays_deterministic():
    """The float override pins the clock for tests / historical
    replays, exactly as before the fix."""
    fs = synth_filesystem(300, n_dirs=30, seed=0, now=1.7e9)
    idx = PrimaryIndex()
    idx.ingest_table(fs, 1)
    q = QueryEngine(idx, AggregateIndex(), now=1.7e9)
    assert q.now == 1.7e9
    first = sorted(q.not_accessed_since(90 * 86400))
    time.sleep(0.01)
    assert sorted(q.not_accessed_since(90 * 86400)) == first
    q.now = 1.7e9 + 400 * 86400       # reassignment still works
    assert len(q.not_accessed_since(90 * 86400)) >= len(first)


def test_now_defaults_to_wallclock():
    q = QueryEngine(PrimaryIndex(), AggregateIndex())
    before = time.time()
    got = q.now
    assert before - 1.0 <= got <= time.time() + 1.0


def test_duplicate_grouping_many_small_groups_identical_and_fast():
    """ISSUE 7 regression: ``duplicate_candidates`` grouped via an
    ``inv == ui`` rescan of the full inverse array per duplicated group
    — O(groups * n). On a dedup-heavy corpus (every file has exactly
    one twin) that is quadratic: ~19s at 250k rows on the old code vs
    ~0.2s for the argsort + boundary-scan grouping. The assert below is
    a generous absolute bound the old implementation cannot meet, plus
    full equality against a brute-force dict oracle (keys AND within-
    group path order)."""
    n = 250_000
    idx = PrimaryIndex()
    paths = [f"/fs/dup/f{i}" for i in range(n)]
    fields = {
        # synthetic checksums: rows 2i and 2i+1 are twins
        "path_hash": (np.arange(n, dtype=np.uint32) // 2),
        "size": np.ones(n, np.float32),
    }
    idx.upsert_batch(paths, fields, np.full(n, 1, np.int64))
    q = QueryEngine(idx, AggregateIndex(), now=1.7e9)
    t0 = time.perf_counter()
    dup = q.duplicate_candidates()
    elapsed = time.perf_counter() - t0

    live = idx.live()
    expect = {}
    for hsh, p in zip(live["path_hash"], live["path"]):
        expect.setdefault(int(hsh), []).append(p)
    expect = {k: v for k, v in expect.items() if len(v) > 1}
    assert len(dup) == n // 2
    assert set(dup) == set(expect)
    for k, want in expect.items():
        assert list(dup[k]) == want
    assert elapsed < 8.0, f"duplicate grouping took {elapsed:.1f}s"


def _size_paths(q, threshold, route):
    """large_cold_files with an always-true idle window: isolates the
    size predicate on the requested route."""
    got = sorted(q.large_cold_files(threshold, -1e12))
    assert q.last_plan["route"] == route, q.last_plan
    return got


def test_float32_size_threshold_boundaries_agree_across_routes():
    """ISSUE 7 satellite: directed boundary test at sizes straddling
    2**24 (first float32 gap > 1) and 2**53 (first float64-int gap).
    The storage dtype is float32 — DESIGN.md §13.5's contract is that
    every route answers AS IF sizes were float32, identically: the
    scan, the fused kernel, and the discovery index must agree at
    thresholds on and off the f32 grid."""
    near24 = 2.0 ** 24          # f32 spacing 2 beyond this
    near53 = 2.0 ** 53
    sizes = [near24 - 2, near24 - 1, near24, near24 + 2, near24 + 3,
             near53, near53 + 1, 2 * near53]
    paths = [f"/fs/b/f{i}" for i in range(len(sizes))]
    # near24 + 1.5 is NOT on the f32 grid: the contract (§13.5) rounds
    # the threshold to the storage dtype before comparing (numpy weak-
    # scalar promotion: f32 column > python float compares in f32), so
    # stored 2^24+2 does NOT exceed it — on every route alike
    thresholds = [near24 - 1, near24, near24 + 1, near24 + 1.5,
                  near24 + 2, near24 + 2.5, near53 - 1, near53,
                  near53 + 1]

    def build(use_kernels, discovery):
        idx = PrimaryIndex()
        put(idx, paths, sizes, atime=[0.0] * len(sizes))
        if discovery:
            idx.attach_discovery()
            idx.rebuild_discovery()
        return QueryEngine(idx, AggregateIndex(), now=1.7e9,
                           use_kernels=use_kernels)

    scan = build(False, False)
    kern = build(True, False)
    disc = build(False, True)
    f32 = np.array(sizes, np.float32)
    for t in thresholds:
        want = sorted(np.array(paths)[f32 > np.float32(t)])
        assert _size_paths(scan, t, "scan") == want, t
        assert _size_paths(kern, t, "kernel") == want, t
        assert _size_paths(disc, t, "discovery") == want, t


def test_unknown_query_errors_list_the_full_allowlist():
    """Both dispatch doors (``query`` and ``select_many``) reject an
    unknown name with the SORTED allowlist in the message — and the
    rollup queries (ISSUE 8) are registered in it, so a caller typo'ing
    ``du`` discovers the real name from the error itself."""
    import pytest

    q = QueryEngine(PrimaryIndex(), AggregateIndex(), now=1.7e9)
    want = str(sorted(q.QUERY_METHODS))
    for new in ("du", "subtree_summary", "hot_directories"):
        assert new in q.QUERY_METHODS
    with pytest.raises(ValueError) as e1:
        q.query("disk_usage")
    with pytest.raises(ValueError) as e2:
        q.select_many([("disk_usage", (), {})])
    for err in (str(e1.value), str(e2.value)):
        assert "disk_usage" in err and want in err


def test_merge_freshness_defaults_partial_marks():
    """Regression (ISSUE 10 satellite): ``merge_freshness`` hard-indexed
    ``applied_seq`` / ``pending_events`` / ``staleness_s`` and KeyErrored
    on a mark from a layer that only exports lag fields, while every
    LATER key was ``.get``-defaulted. Partial marks must degrade the
    merge (applied_seq pins at 0 — "can't vouch for anything newer"),
    never crash it."""
    from repro.core.query import merge_freshness

    partial = {"mode": "policy", "log_lag": 3, "replica_lag": 2}
    merged = merge_freshness([partial])          # used to KeyError here
    assert merged["applied_seq"] == 0
    assert merged["pending_events"] == 0
    assert merged["staleness_s"] == 0.0
    assert merged["log_lag"] == 3 and merged["replica_lag"] == 2

    full = {"mode": "eager", "applied_seq": 9, "pending_events": 1,
            "staleness_s": 0.5}
    both = merge_freshness([partial, full])
    assert both["applied_seq"] == 0              # min over sources
    assert both["pending_events"] == 1           # sums
    assert both["staleness_s"] == 0.5            # max
    assert both["sources"] == 2
