"""Continuous policy engine (core/policy.py, DESIGN.md §14.4).

Covers: rule validation, conservative retention bucket semantics at the
pinned clock (REF_TIME = 1.7e9), dirty-subtree-only re-evaluation
(asserted via the evaluated/skipped counters — the acceptance
criterion), uid-quota watermark gating, enter/exit edge delivery, scan
fallback, agreement with the Robinhood-style full-scan baseline, and
the dashboard/monitor surfaces.
"""
import pytest

from repro.core import events as ev
from repro.core import hierarchy as hier
from repro.core.dashboard import du_view, policy_panel, render_dashboard
from repro.core.index import AggregateIndex, PrimaryIndex
from repro.core.policy import PolicyEngine, Rule, retention_min_bucket
from repro.core.query import QueryEngine
from test_query_fixes import put
from test_rollup import drive

DAY = 86400.0


# ---------------------------------------------------------------------------
# rule validation + bucket semantics
# ---------------------------------------------------------------------------

def test_rule_validation():
    with pytest.raises(ValueError, match="unknown rule kind"):
        Rule("r", "min_bytes", limit_bytes=1)
    with pytest.raises(ValueError, match="requires 'limit_bytes'"):
        Rule("r", "max_bytes")
    with pytest.raises(ValueError, match="requires 'max_age_s'"):
        Rule("r", "retention")
    with pytest.raises(ValueError, match="requires 'uid'"):
        Rule("r", "uid_quota", limit_bytes=1)
    with pytest.raises(ValueError, match="unique"):
        PolicyEngine([Rule("a", "max_bytes", limit_bytes=1),
                      Rule("a", "retention", max_age_s=1.0)])


def test_retention_min_bucket_is_conservative():
    """Bucket b spans ages [edge[b-1], edge[b]): only buckets ENTIRELY
    past the limit count, so boundary limits round AWAY from firing."""
    assert retention_min_bucket(7 * DAY) == 1     # [7d,30d) all >= 7d
    assert retention_min_bucket(6.9 * DAY) == 1   # [0,7d) straddles: out
    assert retention_min_bucket(90 * DAY) == 3
    assert retention_min_bucket(91 * DAY) == 4    # [90d,180d) straddles
    assert retention_min_bucket(730 * DAY) == 6
    # beyond the last edge nothing is provably over age: never fires
    assert retention_min_bucket(800 * DAY) == hier.N_ATIME_BUCKETS


def test_retention_fires_on_scan_route_at_pinned_clock():
    """No hierarchy attached: verdicts come from the brute-force scan.
    Ages are judged against REF_TIME (= 1.7e9, the repo's pinned query
    clock); a file idle 800 days violates a 730-day retention rule, a
    60-day-idle file does not."""
    idx = PrimaryIndex()
    put(idx, ["/fs/proj/old", "/fs/proj/warm"], [10.0, 20.0],
        atime=[hier.REF_TIME - 800 * DAY, hier.REF_TIME - 60 * DAY])
    eng = PolicyEngine(
        [Rule("ret730", "retention", path="/fs/proj", max_age_s=730 * DAY),
         Rule("ret2000", "retention", path="/fs/proj",
              max_age_s=2000 * DAY)],
        primary=idx)
    edges = eng.evaluate()
    assert [e["rule"] for e in edges] == ["ret730"]
    v = eng.violations()
    assert v["ret730"]["files_over_age"] == 1
    assert "ret2000" not in v             # nothing provably > 2000d


def test_engine_without_tree_or_primary_raises():
    eng = PolicyEngine([Rule("q", "max_bytes", limit_bytes=1)])
    with pytest.raises(RuntimeError, match="no exact hierarchy"):
        eng.evaluate()
    eng2 = PolicyEngine([Rule("u", "uid_quota", limit_bytes=1, uid=0)])
    with pytest.raises(RuntimeError, match="aggregate or primary"):
        eng2.evaluate()


# ---------------------------------------------------------------------------
# incrementality: only dirty subtrees re-judged (the acceptance counter)
# ---------------------------------------------------------------------------

def test_sweep_skips_unchanged_subtrees():
    primary, ing, stream = drive("eager", None, split_frac=0.0, seed=41)
    h = ing.hierarchy
    live = primary.live()
    by_path = {}
    fids = list(ing._name)
    for p, f in zip(hier.resolve_paths_host(ing._parent, ing._name, fids),
                    fids):
        if p is not None:
            by_path[p] = f
    # two sibling subtrees with files in each
    dirs = sorted({hier._dirname(str(p)) for p in live["path"]
                   if str(p) in by_path and hier._dirname(str(p)) != "/fs"})
    d_a, d_b = dirs[0], dirs[-1]
    assert d_a != d_b
    victim = next(str(p) for p in live["path"]
                  if hier._dirname(str(p)) == d_a and str(p) in by_path)

    eng = PolicyEngine(
        [Rule("quota_a", "max_bytes", path=d_a, limit_bytes=1 << 60),
         Rule("quota_b", "max_bytes", path=d_b, limit_bytes=1 << 60),
         Rule("ret_b", "retention", path=d_b, max_age_s=730 * DAY)],
        hierarchy=h, primary=primary)

    eng.evaluate()                        # first sweep judges everything
    assert eng.stats == {**eng.stats, "evaluated": 3, "skipped": 0}
    eng.evaluate()                        # nothing moved: all gated
    assert eng.stats["skipped"] == 3 and eng.stats["evaluated"] == 3

    # touch ONE file under d_a; d_b's marks must still gate its rules
    stream.emit(ev.E_SATTR, by_path[victim], has_stat=1,
                size=7777.0, mtime=9.5e5)
    ing.ingest(stream.take(4))
    ing.flush()
    before_eval, before_skip = eng.stats["evaluated"], eng.stats["skipped"]
    eng.evaluate()
    assert eng.stats["evaluated"] == before_eval + 1   # quota_a only
    assert eng.stats["skipped"] == before_skip + 2     # both d_b rules


def test_uid_quota_gates_on_watermark_not_subtree_marks():
    """A chown-style change moves per-user totals without touching any
    subtree rollup, so uid rules key on the ingest watermark: same
    watermark -> skip, new watermark -> re-judge (even with no tree)."""
    agg = AggregateIndex()
    agg.records["user:3"] = {"size": {"total": 900.0}}
    eng = PolicyEngine(
        [Rule("u3", "uid_quota", uid=3, limit_bytes=500)], aggregate=agg)

    edges = eng.evaluate(watermark=10)
    assert edges and edges[0]["edge"] == "enter"
    eng.evaluate(watermark=10)            # unchanged wm: gated
    assert eng.stats["skipped"] == 1
    agg.records["user:3"] = {"size": {"total": 100.0}}
    edges = eng.evaluate(watermark=11)    # wm moved: re-judged -> exit
    assert edges and edges[0]["edge"] == "exit"
    assert eng.violations() == {}
    # None watermark disables the gate entirely
    eng.evaluate()
    assert eng.stats["evaluated"] == 3


def test_edge_delivery_is_per_transition():
    """enter on rising edge, exit on falling edge, silence while level
    holds; drain_events empties the deque but ``active`` keeps truth."""
    agg = AggregateIndex()
    agg.records["user:1"] = {"size": {"total": 10.0}}
    eng = PolicyEngine(
        [Rule("u1", "uid_quota", uid=1, limit_bytes=50)], aggregate=agg)
    assert eng.evaluate() == []           # under limit: no edge
    agg.records["user:1"] = {"size": {"total": 99.0}}
    assert [e["edge"] for e in eng.evaluate()] == ["enter"]
    assert eng.evaluate() == []           # still violated: level, no edge
    assert eng.violations()["u1"]["used_bytes"] == 99
    got = eng.drain_events()
    assert [e["edge"] for e in got] == ["enter"] and not eng.events
    assert eng.violations()["u1"]          # drain does not clear level
    agg.records["user:1"] = {"size": {"total": 1.0}}
    assert [e["edge"] for e in eng.evaluate()] == ["exit"]
    assert eng.stats["enter"] == 1 and eng.stats["exit"] == 1


# ---------------------------------------------------------------------------
# agreement with the full-scan baseline
# ---------------------------------------------------------------------------

def test_incremental_verdicts_match_full_scan_baseline():
    primary, ing, _ = drive("eager", 4, split_frac=0.0, seed=47)
    h = ing.hierarchy
    total = h.du("/fs")["total_bytes"]
    rules = [
        Rule("ns_cap_tight", "max_bytes", path="/fs",
             limit_bytes=max(total // 2, 1)),
        Rule("ns_cap_loose", "max_bytes", path="/fs", limit_bytes=1 << 60),
        Rule("ret", "retention", path="", max_age_s=365 * DAY),
        Rule("u1_tight", "uid_quota", uid=1, limit_bytes=0),
        Rule("u1_loose", "uid_quota", uid=1, limit_bytes=1 << 60),
    ]
    eng = PolicyEngine(rules, hierarchy=h, primary=primary)
    eng.evaluate(watermark=1)
    incremental = {r.name: r.name in eng.violations() for r in rules}
    assert incremental == eng.full_scan_baseline()
    assert incremental["ns_cap_tight"] and not incremental["ns_cap_loose"]


# ---------------------------------------------------------------------------
# surfaces: dashboard panels + monitor loop
# ---------------------------------------------------------------------------

def test_dashboard_du_and_policy_panels():
    primary, ing, _ = drive("eager", None, split_frac=0.0, seed=51)
    h = ing.hierarchy
    eng = PolicyEngine([Rule("cap", "max_bytes", path="/fs",
                             limit_bytes=1)],
                       hierarchy=h, primary=primary)
    eng.evaluate()
    q = QueryEngine(primary, AggregateIndex(), now=1.7e9, ingestor=ing)
    txt = du_view(q, "/fs", depth=1)
    assert txt.startswith("== du /fs ==") and q.last_plan["route"] == \
        "rollup"
    panel = policy_panel(eng)
    assert "1 violation active" in panel and "VIOLATED cap" in panel
    dash = render_dashboard(primary, AggregateIndex(), now=1.7e9,
                            policy=eng, hierarchy=h, du_paths=("/fs",))
    assert "== du /fs ==" in dash and "VIOLATED cap" in dash
    # the add-on panels default OFF: legacy callers render unchanged
    assert "du /fs" not in render_dashboard(primary, AggregateIndex(),
                                            now=1.7e9)


def test_monitor_drives_policy_sweeps_per_batch():
    from repro.core.monitor import Monitor, MonitorConfig
    from test_differential import PCFG, gen_workload
    from repro.core.event_ingest import EventIngestor, IngestConfig

    stream = ev.EventStream(start_fid=1)
    gen_workload(stream, 120, seed=53)
    names = {0: "fs", **stream.names}
    primary = PrimaryIndex()
    ing = EventIngestor(
        IngestConfig(mode="eager", pad_to=64, max_buffer_events=150,
                     freshness_window=1e9, update_aggregates=False),
        PCFG, primary, AggregateIndex(), names=names)
    # "cap" roots at the churning namespace (re-judged every batch);
    # "quiet" roots at an untouched subtree (gated after sweep one)
    eng = PolicyEngine([Rule("cap", "max_bytes", path="/fs",
                             limit_bytes=1),
                        Rule("quiet", "max_bytes", path="/archive",
                             limit_bytes=1 << 60)],
                       hierarchy=ing.hierarchy, primary=primary)
    mon = Monitor(MonitorConfig(max_fids=1 << 12, batch_size=64),
                  ingestor=ing, policy=eng)
    out = mon.run(stream)
    assert eng.stats["sweeps"] == mon.metrics["batches"] > 0
    assert eng.stats["skipped"] == eng.stats["sweeps"] - 1  # "quiet" gated
    assert out["policy_violations"] == 1 and out["policy_sweeps"] > 0
    assert out["rollup_exact"] and "cap" in eng.violations()
