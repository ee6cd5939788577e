"""Subtree-rollup differential oracle (ISSUE 8 flagship).

The incrementally-maintained ``HierarchyIndex`` must answer du /
subtree_summary / hot_directories **byte-identically** to a brute-force
recompute over the primary's ``live()`` view — after random event
suffixes, across eager/buffered consistency modes x mono/4-shard
layouts, through a mid-stream snapshot handoff, a lossy feed repaired
by anti-entropy, tombstone compaction, and checkpoint -> crash ->
restore. Incrementality is asserted against the tree's propagation
work counter, not wall clock.
"""
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import events as ev
from repro.core import hierarchy as hier
from repro.core.event_ingest import EventIngestor, IngestConfig
from repro.core.hierarchy import resolve_paths_host
from repro.core.index import AggregateIndex
from repro.core.metadata import TYPE_DIR, synth_filesystem
from repro.core.policy import PolicyEngine, Rule
from repro.core.query import QueryEngine
from repro.core.query_service import QueryService
from repro.core.reconcile import compact_if_needed, reconcile
from test_differential import PCFG, RefState, gen_workload, make_primary


def _mk_ing(mode, primary, names):
    return EventIngestor(
        IngestConfig(mode=mode, pad_to=64, max_buffer_events=150,
                     freshness_window=1e9, update_aggregates=False),
        PCFG, primary, AggregateIndex(), names=names)


def _sample_dirs(live, k=6):
    """A few real directory paths, deepest first (plus both roots)."""
    dirs = sorted({hier._dirname(str(p)) for p in live["path"]},
                  key=lambda d: (-d.count("/"), d))
    return ["", "/fs"] + dirs[:k]


def assert_rollup_equals_scan(h, primary, ctx=""):
    """The full proof obligation at one instant: every rollup query,
    on several subtree roots, byte-equal to the scan oracle."""
    assert h is not None and h.exact, ctx
    live = primary.live()
    for p in _sample_dirs(live):
        assert h.du(p, depth=8) == hier.du_scan(live, p, depth=8), (ctx, p)
        assert h.subtree_summary(p) == \
            hier.subtree_summary_scan(live, p), (ctx, p)
    assert h.hot_directories(k=16) == \
        hier.hot_directories_scan(live, k=16), ctx
    assert h.validate_depths(), ctx


def drive(mode, n_shards, split_frac, seed, n_ops=400):
    """Replay a random workload (optionally from a mid-stream snapshot
    handoff) and return (primary, ingestor, stream)."""
    stream = ev.EventStream(start_fid=1)
    gen_workload(stream, n_ops, seed)
    names = {0: "fs", **stream.names}
    batches = []
    while len(stream):
        batches.append(stream.take(64))
    n_prefix = int(split_frac * sum(len(b["seq"]) for b in batches))
    ref = RefState(names)
    primary = make_primary(n_shards)
    ing = _mk_ing(mode, primary, names)
    seen, snap_done = 0, n_prefix == 0
    for b in batches:
        if not snap_done:
            ref.apply_batch(b)
            seen += len(b["seq"])
            if seen >= n_prefix:
                primary.ingest_table(ref.table(),
                                     version=int(b["seq"].max()))
                ing.register_tree(parents=dict(ref.parent),
                                  names=dict(ref.name),
                                  is_dir=dict(ref.isdir))
                snap_done = True
            continue
        ing.ingest(b)
    ing.flush()
    return primary, ing, stream


# ---------------------------------------------------------------------------
# satellite: resolve_paths_host failure modes
# ---------------------------------------------------------------------------

def test_resolve_paths_host_raises_on_parent_cycle():
    """A directed parent cycle (1 -> 2 -> 1) must raise, not silently
    truncate into a 256-component path."""
    parent = {1: 2, 2: 1}
    name = {1: "a", 2: "b"}
    with pytest.raises(ValueError, match="cycle"):
        resolve_paths_host(parent, name, [1])


def test_resolve_paths_host_raises_on_depth_overflow():
    chain = {i: i - 1 for i in range(1, 40)}
    chain[0] = -1
    name = {i: f"d{i}" for i in range(40)}
    with pytest.raises(ValueError, match="depth"):
        resolve_paths_host(chain, name, [39], max_depth=10)


def test_resolve_paths_host_unknowns_are_none_not_placeholders():
    """Unknown fids (and fids whose ancestor chain hits an unnamed
    node) resolve to an explicit None entry — no '#fid' placeholders."""
    parent = {1: 0, 0: -1, 7: 99}        # 99 never named: 7 unresolvable
    name = {0: "fs", 1: "d1", 7: "d7"}
    got = resolve_paths_host(parent, name, [1, 5, 7])
    assert got[0] == "/fs/d1"
    assert got[1] is None                # never seen at all
    assert got[2] is None                # dangling ancestor


# ---------------------------------------------------------------------------
# the differential matrix: rollups == brute force
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["eager", "buffered"])
@pytest.mark.parametrize("n_shards", [None, 4])
@pytest.mark.parametrize("split_frac", [0.0, 0.5])
def test_rollup_matches_scan_matrix(mode, n_shards, split_frac):
    """Event replay (pure and snapshot-handoff) across the mode x shard
    matrix: the rollup tree stays exact and byte-equals brute force."""
    primary, ing, _ = drive(mode, n_shards, split_frac, seed=7)
    assert_rollup_equals_scan(
        ing.hierarchy, primary,
        f"mode={mode} shards={n_shards} split={split_frac}")


@settings(max_examples=8, deadline=None)
@given(st.integers(0, 10_000), st.sampled_from([None, 2, 4]),
       st.sampled_from(["eager", "buffered"]))
def test_rollup_property_sweep(seed, n_shards, mode):
    """Randomized corpora x interleavings x shard layouts (hypothesis
    sweep): creates, updates, renames, deletes, mkdirs in random mixes
    must never desync the rollups from the scan oracle."""
    primary, ing, _ = drive(mode, n_shards, split_frac=0.3, seed=seed,
                         n_ops=260)
    assert_rollup_equals_scan(ing.hierarchy, primary,
                              f"seed={seed} shards={n_shards} mode={mode}")


def test_rollup_survives_reconcile_repairs():
    """Lossy feed (25% dropped events) + one anti-entropy pass: repairs
    flow through ``apply_repairs`` sync ops and the mirror converges to
    the repaired primary — still byte-equal, still exact."""
    stream = ev.EventStream(start_fid=1)
    gen_workload(stream, 350, seed=13)
    names = {0: "fs", **stream.names}
    ref = RefState(names)
    primary = make_primary(3)
    ing = _mk_ing("eager", primary, names)
    rng = np.random.default_rng(99)
    max_seq = 0
    while len(stream):
        b = stream.take(64)
        ref.apply_batch(b)
        max_seq = max(max_seq, int(b["seq"].max()))
        keep = rng.random(len(b["seq"])) >= 0.25
        kept = {k: v[keep] for k, v in b.items()}
        if len(kept["seq"]):
            ing.ingest(kept)
    ing.flush()
    rep = reconcile(ref.table(), version=max_seq, ingestor=ing)
    assert rep.repairs > 0               # the drops really drifted it
    assert_rollup_equals_scan(ing.hierarchy, primary, "reconcile")


def test_rollup_survives_compaction():
    """Compaction rewrites slots but no live record: the path-keyed
    rollups are untouched and stay exact."""
    primary, ing, _ = drive("eager", 3, split_frac=0.0, seed=29)
    h = ing.hierarchy
    before = h.du("/fs", depth=4)
    assert primary.slot_stats()["dead"] > 0
    compact_if_needed(primary, threshold=0.0, ingestor=ing)
    assert h.exact and h.stats["compactions"] > 0
    assert h.du("/fs", depth=4) == before
    assert_rollup_equals_scan(h, primary, "compaction")


def test_bulk_ingest_invalidates_then_register_tree_reseeds():
    """Out-of-band bulk load flips ``exact`` off (queries fall back to
    the scan route); ``register_tree`` reseeds and restores the rollup
    route — answers identical on both sides of the transition."""
    stream = ev.EventStream(start_fid=1)
    gen_workload(stream, 300, seed=5)
    names = {0: "fs", **stream.names}
    ref = RefState(names)
    while len(stream):
        ref.apply_batch(stream.take(64))
    primary = make_primary(None)
    ing = _mk_ing("eager", primary, names)
    primary.ingest_table(ref.table(), version=7)
    assert not ing.hierarchy.exact       # invalidate_older -> _mutated(None)

    q = QueryEngine(primary, AggregateIndex(), now=1.7e9, ingestor=ing)
    scan_ans = q.du("/fs", depth=3)
    assert q.last_plan["route"] == "scan"

    ing.register_tree(parents=dict(ref.parent), names=dict(ref.name),
                      is_dir=dict(ref.isdir))
    assert ing.hierarchy.exact
    assert q.du("/fs", depth=3) == scan_ans
    assert q.last_plan["route"] == "rollup"
    assert_rollup_equals_scan(ing.hierarchy, primary, "reseed")


def test_propagation_is_incremental():
    """After a refresh, one file touch costs a propagation walk bounded
    by the owning dir's ancestor chain — not a subtree recompute. The
    acceptance criterion's work-counter assertion."""
    primary, ing, stream = drive("eager", None, split_frac=0.0, seed=3)
    h = ing.hierarchy
    h.refresh()                          # drain startup dirt
    assert h.dirty_count() == 0
    n_nodes = h._n

    # map live paths back to fids via the ingestor's parent/name tables,
    # preferring the deepest victim so the bound is non-trivial
    fids = list(ing._name)
    by_path = dict(zip(resolve_paths_host(ing._parent, ing._name, fids),
                       fids))
    live = primary.live()
    victim = max((str(p) for p in live["path"] if str(p) in by_path),
                 key=lambda p: p.count("/"))
    assert victim.count("/") >= 2

    # one SATTR on the same stream (seq stays monotonic past the drive)
    before = h.stats["propagated"]
    stream.emit(ev.E_SATTR, by_path[victim], has_stat=1, size=12345.0,
                mtime=9.0e5)
    ing.ingest(stream.take(4))
    ing.flush()
    h.refresh()
    work = h.stats["propagated"] - before
    depth_bound = victim.count("/") + 1  # owning dir + its ancestors
    assert 0 < work <= depth_bound, (work, depth_bound)
    assert work < n_nodes / 2            # nowhere near a full recompute
    assert_rollup_equals_scan(h, primary, "incremental touch")


# ---------------------------------------------------------------------------
# a deep snapshot tree handed to the event path
# ---------------------------------------------------------------------------

N_CHURN, CHURN_FILES = 4, 50


def build_deep(n_files=6000, n_dirs=600):
    """Snapshot-ingest a tree at least 8 directories deep, then hand off
    to the event path: ``register_tree`` reseeds the rollups and
    registers every directory plus the files churn will touch (fid =
    table row). Returns (table, primary, ingestor, churn victims)."""
    table = synth_filesystem(n_files, n_dirs=n_dirs, max_depth=12, seed=8)
    assert int(table.depth.max()) >= 8
    primary = make_primary(None)
    primary.ingest_table(table, version=0)
    ing = EventIngestor(
        IngestConfig(mode="eager", pad_to=64, max_buffer_events=256,
                     freshness_window=1e9, update_aggregates=False),
        PCFG, primary, AggregateIndex())
    dirs = np.flatnonzero(table.type == TYPE_DIR)
    victims = np.random.default_rng(17).choice(
        np.flatnonzero(table.type != TYPE_DIR), size=N_CHURN * CHURN_FILES,
        replace=False)
    rows = np.concatenate([dirs, victims])
    ing.register_tree(
        parents={int(r): int(table.parent[r]) for r in rows},
        names={int(r): str(table.paths[r]).rsplit("/", 1)[-1] if r else "fs"
               for r in rows},
        is_dir={int(r): True for r in dirs})
    assert ing.hierarchy.exact
    return table, primary, ing, victims


def test_deep_tree_du_matches_scan_at_every_depth():
    """``du`` through the rollups equals the scan over ``live()`` at
    every depth of the tree, from the root and from two mid-depth
    directories with large subtrees."""
    table, primary, ing, _ = build_deep()
    h = ing.hierarchy
    q = QueryEngine(primary, AggregateIndex(), now=1.7e9, ingestor=ing)
    mids = [r["path"] for r in h.hot_directories(
        k=64, buckets=hier.N_ATIME_BUCKETS) if 2 <= r["path"].count("/") <= 4]
    assert len(mids) >= 2
    live = primary.live()
    for path in ["/fs"] + mids[:2]:
        for d in range(int(table.depth.max()) + 1):
            assert q.du(path, depth=d) == hier.du_scan(live, path, depth=d), \
                (path, d)
            assert q.last_plan["route"] == "rollup", (path, q.last_plan)


def test_deep_tree_policy_sweeps_under_churn_match_full_scan():
    """Incremental policy sweeps, one per churn batch of stat updates,
    reach the same verdicts as the full-namespace scan baseline, and
    each batch's propagation touches well under half of the tree."""
    _, primary, ing, victims = build_deep()
    h = ing.hierarchy
    proj = [r["path"] for r in h.hot_directories(k=8)]
    rules = [Rule(f"proj{i}", "max_bytes", path=p, limit_bytes=1 << 44)
             for i, p in enumerate(proj)]
    rules += [Rule("ret2y", "retention", path="/fs", max_age_s=730 * 86400),
              Rule("u1", "uid_quota", uid=1, limit_bytes=1 << 62),
              Rule("u2_tight", "uid_quota", uid=2, limit_bytes=1)]
    eng = PolicyEngine(rules, hierarchy=h, primary=primary)
    eng.evaluate(watermark=0)
    stream = ev.EventStream(start_fid=len(primary) + 1_000_000)
    for i in range(N_CHURN):
        for r in victims[i * CHURN_FILES:(i + 1) * CHURN_FILES]:
            stream.emit(ev.E_SATTR, int(r), has_stat=1,
                        size=float(1024 + r % 4096), mtime=1.7e9 - 3600.0)
        before = h.stats["propagated"]
        ing.ingest(stream.take(None))
        ing.flush()
        eng.evaluate(watermark=int(ing.freshness()["applied_seq"]))
        assert h.stats["propagated"] - before < h._n / 2, i
    verdicts = {r.name: r.name in eng.violations() for r in rules}
    assert verdicts == eng.full_scan_baseline()
    assert verdicts["u2_tight"]                # a violation really fired
    assert_rollup_equals_scan(h, primary, "deep churn")


def test_rollup_state_roundtrip_is_byte_identical():
    """state_dict -> load_state reproduces the tree exactly (arrays,
    paths, file registry, exactness, apply epoch)."""
    primary, ing, _ = drive("buffered", 4, split_frac=0.5, seed=17)
    st1 = ing.hierarchy.state_dict()
    ing2 = _mk_ing("buffered", primary, None)
    ing2.load_state(ing.state_dict())
    assert ing2.hierarchy.state_dict() == st1
    assert ing2.hierarchy.exact
    assert ing2.hierarchy.du("/fs", depth=6) == \
        ing.hierarchy.du("/fs", depth=6)


def test_restore_of_pre_rollup_checkpoint_falls_back_to_scan():
    """A checkpoint written before the rollup layer existed restores as
    None: the tree resets inexact and queries scan — no crash, no lie."""
    primary, ing, _ = drive("eager", None, split_frac=0.0, seed=11)
    state = ing.state_dict()
    state["hierarchy"] = None            # what an old checkpoint carries
    ing2 = _mk_ing("eager", primary, None)
    ing2.load_state(state)
    assert not ing2.hierarchy.exact
    q = QueryEngine(primary, AggregateIndex(), now=1.7e9, ingestor=ing2)
    assert q.du("/fs") == hier.du_scan(primary.live(), "/fs")
    assert q.last_plan["route"] == "scan"


# ---------------------------------------------------------------------------
# crash-recovery leg (the PR-4 fault-injection harness)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("point", ["mid_apply", "mid_checkpoint"])
def test_rollups_survive_crash_recovery(point, tmp_path):
    """checkpoint -> crash -> restore -> replay must leave the rollup
    tree byte-identical (state_dict) to the uninterrupted run's, and
    byte-equal to brute force over the recovered primary."""
    from test_crash_recovery import _drive

    o_primary, o_ing, crashes = _drive(
        str(tmp_path / "oracle.ckpt"), "eager", 4, kills=(), seed=11)
    assert crashes == 0
    primary, ing, crashes = _drive(
        str(tmp_path / "crash.ckpt"), "eager", 4,
        kills=[(point, 1), (point, 1)], seed=11)
    assert crashes == 2
    assert ing.hierarchy.state_dict() == o_ing.hierarchy.state_dict()
    assert_rollup_equals_scan(ing.hierarchy, primary, f"crash@{point}")


# ---------------------------------------------------------------------------
# serving tier: rollup queries join the watermark-keyed cache
# ---------------------------------------------------------------------------

def test_service_caches_rollup_queries_and_invalidates_on_apply():
    stream = ev.EventStream(start_fid=1)
    gen_workload(stream, 300, seed=21)
    names = {0: "fs", **stream.names}
    primary = make_primary(None)
    ing = _mk_ing("eager", primary, names)
    batches = []
    while len(stream):
        batches.append(stream.take(64))
    for b in batches[:-1]:
        ing.ingest(b)
    ing.flush()

    svc = QueryService(primary, AggregateIndex(), ingestor=ing, now=1.7e9)
    r1 = svc.query("du", "/fs", depth=2)
    r2 = svc.query("du", "/fs", depth=2)
    assert r1["result"] == r2["result"]
    assert not r1["freshness"]["cached"] and r2["freshness"]["cached"]
    assert r1["result"] == hier.du_scan(primary.live(), "/fs", depth=2)

    ing.ingest(batches[-1])              # mutating apply -> version bump
    ing.flush()
    r3 = svc.query("du", "/fs", depth=2)
    assert not r3["freshness"]["cached"]
    assert r3["result"] == hier.du_scan(primary.live(), "/fs", depth=2)

    batch = svc.query_batch([("du", "/fs"), ("subtree_summary", "/fs"),
                             ("hot_directories",)])
    assert batch[0]["result"] == hier.du_scan(primary.live(), "/fs")
    assert batch[1]["result"] == \
        hier.subtree_summary_scan(primary.live(), "/fs")
    assert batch[2]["result"] == hier.hot_directories_scan(primary.live())
    svc.close()


def test_freshness_carries_rollup_marks():
    primary, ing, _ = drive("eager", None, split_frac=0.0, seed=9)
    fr = ing.freshness()
    assert fr["rollup_exact"]
    assert fr["rollup_dirty"] >= 0
