"""Property sweep for MVCC snapshot isolation (ISSUE 6 satellite).

Random interleavings of every mutation class the index supports —
upsert / delete / rename / compaction / checkpoint-restore — with
snapshot open / query / close, on the monolithic and sharded layouts.
The invariants:

- an open snapshot NEVER changes its answers, whatever happens to the
  live index after the pin (including arena growth, slot renumbering by
  compaction, and wholesale state replacement by restore);
- the serving tier's watermark tokens are monotone non-decreasing, and
  a mutation observed by a query implies a token advance;
- cursor pagination during ingest never skips or duplicates rows: the
  concatenated pages equal the full query result at the cursor's pinned
  watermark, exactly;
- closing every snapshot returns pin refcounts to baseline and disarms
  copy-on-write.
"""
import itertools

import numpy as np
from hypothesis import given, settings, strategies as st

import test_differential as td
from repro.core.index import AggregateIndex, PrimaryIndex
from repro.core.query import QueryEngine
from repro.core.sharded_index import index_from_state

from test_query_service import (NOW, assert_same_result, build_workload,
                                make_service)


def frozen_live(primary):
    """A deep copy of the live view (the per-snapshot oracle)."""
    return {k: np.array(v, copy=True) for k, v in primary.live().items()}


def check_snap(snap, expected, ctx):
    got = snap.live()
    assert set(got) == set(expected), ctx
    for k in expected:
        assert got[k].dtype == expected[k].dtype, (ctx, k)
        assert np.array_equal(got[k], expected[k]), (ctx, k)
    assert len(snap) == len(expected["path"]), ctx


# ---------------------------------------------------------------------------
# index-level isolation: every mutation class vs open snapshots
# ---------------------------------------------------------------------------

@settings(max_examples=10, deadline=None)
@given(st.integers(min_value=0, max_value=2**31),
       st.sampled_from([None, 4]))
def test_snapshots_frozen_under_random_interleavings(seed, n_shards):
    rng = np.random.default_rng(seed)
    primary = td.make_primary(n_shards)
    pool = [f"/t/p{i:03d}" for i in range(48)]
    ver = itertools.count(1)
    snaps = []                      # (snap, frozen expected live view)
    ckpt = None

    def rand_fields():
        return {"size": float(np.float32(rng.gamma(1.5, 1e4))),
                "mtime": float(np.float32(rng.uniform(1, 1e6))),
                "uid": int(rng.integers(0, 8)),
                "gid": int(rng.integers(0, 4))}

    for step in range(70):
        r = rng.random()
        if r < 0.30:                                   # upsert
            primary.upsert(pool[int(rng.integers(len(pool)))],
                           rand_fields(), version=next(ver))
        elif r < 0.42:                                 # delete
            primary.delete(pool[int(rng.integers(len(pool)))],
                           version=next(ver))
        elif r < 0.52:                                 # rename
            src = pool[int(rng.integers(len(pool)))]
            rec = primary.lookup(src)
            if rec is not None:
                dst = pool[int(rng.integers(len(pool)))]
                primary.delete(src, version=next(ver))
                primary.upsert(dst, {k: rec[k] for k in
                                     ("size", "mtime", "uid", "gid")},
                               version=next(ver))
        elif r < 0.60:                                 # compact
            primary.compact()
        elif r < 0.66:                                 # checkpoint
            ckpt = primary.state_dict()
        elif r < 0.72:                                 # restore
            if ckpt is not None:
                primary.load_state(ckpt)
        elif r < 0.84 or not snaps:                    # snapshot open
            s = primary.snapshot()
            snaps.append((s, frozen_live(primary)))
        elif r < 0.94:                                 # snapshot query
            s, exp = snaps[int(rng.integers(len(snaps)))]
            check_snap(s, exp, f"seed={seed} shards={n_shards} "
                               f"step={step}")
        else:                                          # snapshot close
            s, exp = snaps.pop(int(rng.integers(len(snaps))))
            check_snap(s, exp, f"close seed={seed} step={step}")
            s.close()

    for s, exp in snaps:            # every survivor still frozen
        check_snap(s, exp, f"final seed={seed} shards={n_shards}")
        s.close()
    assert primary.snapshot_stats() == {"open_snapshots": 0,
                                        "pinned_epochs": 0}
    shard = primary.shards[0] if n_shards else primary
    primary.upsert("/t/after", rand_fields(), version=next(ver))
    assert not shard._shared        # COW disarmed once nothing is pinned


# ---------------------------------------------------------------------------
# service-level: watermark monotonicity + exact cursors under churn
# ---------------------------------------------------------------------------

@settings(max_examples=6, deadline=None)
@given(st.integers(min_value=0, max_value=2**31),
       st.sampled_from(["eager", "buffered"]))
def test_watermarks_monotone_and_cursors_exact(seed, mode):
    rng = np.random.default_rng(seed)
    n_shards = [None, 4][seed % 2]
    batches, names = build_workload(300, seed=(seed % 97) + 1)
    primary, ing, svc = make_service(mode, n_shards, names)

    oracle = {}

    def record():
        with primary.write_lock():
            oracle.setdefault(svc.data_version, primary.state_dict())

    record()
    feed = list(batches)
    last_wm = -1
    cursors = []                    # [token, watermark, rows collected]
    pinned = None                   # one long-lived snapshot + its answer
    ckpt = None

    for step in range(40):
        r = rng.random()
        if r < 0.35 and feed:                          # ingest
            ing.ingest(feed.pop(0))
            record()
        elif r < 0.45:                                 # flush
            ing.flush()
            record()
        elif r < 0.55:                                 # checkpoint/restore
            if ckpt is None or rng.random() < 0.6:
                ing.flush()          # the checkpoint barrier is an
                record()             # applied-state barrier
                with primary.write_lock():
                    ckpt = (primary.state_dict(), ing.state_dict())
            else:
                with primary.write_lock():
                    primary.load_state(ckpt[0])
                    ing.load_state(ckpt[1])
                record()
        elif r < 0.70:                                 # cached query
            q = svc.query("find_by_glob", "/fs/*f*")
            wm = q["freshness"]["watermark"]
            assert wm >= last_wm, f"token went backwards {last_wm}->{wm}"
            last_wm = wm
            want = QueryEngine(index_from_state(oracle[wm]),
                               AggregateIndex(), now=NOW) \
                .find_by_glob("/fs/*f*")
            assert_same_result(q["result"], want,
                               f"seed={seed} mode={mode} wm={wm}")
        elif r < 0.80:                                 # open a cursor
            record()
            pg = svc.query_page("find_by_glob", "/fs/*",
                                page_size=int(rng.integers(1, 9)))
            rows = list(pg["rows"])
            if pg["cursor"] is not None:
                cursors.append([pg["cursor"], pg["watermark"], rows])
            else:
                check_cursor_rows(oracle, pg["watermark"], rows)
        elif r < 0.92 and cursors:                     # advance a cursor
            c = cursors[int(rng.integers(len(cursors)))]
            pg = svc.query_page(cursor=c[0])
            assert pg["watermark"] == c[1]             # pinned token
            c[2] += list(pg["rows"])
            c[0] = pg["cursor"]
            if c[0] is None:
                cursors.remove(c)
                check_cursor_rows(oracle, c[1], c[2])
        elif pinned is None:                           # pin one snapshot
            pinned = svc.snapshot()
            pinned_want = pinned.engine.find_by_glob("/fs/*")
        if pinned is not None:      # the pin never changes its answer
            assert np.array_equal(pinned.engine.find_by_glob("/fs/*"),
                                  pinned_want)

    for c in cursors:               # drain every open cursor
        while c[0] is not None:
            pg = svc.query_page(cursor=c[0])
            assert pg["watermark"] == c[1]
            c[2] += list(pg["rows"])
            c[0] = pg["cursor"]
        check_cursor_rows(oracle, c[1], c[2])
    if pinned is not None:
        assert np.array_equal(pinned.engine.find_by_glob("/fs/*"),
                              pinned_want)
        pinned.close()
    assert svc.freshness()["open_snapshots"] == 0
    assert svc.freshness()["open_cursors"] == 0
    svc.close()                     # drop the pooled standing pin too
    assert primary.snapshot_stats() == {"open_snapshots": 0,
                                        "pinned_epochs": 0}


def check_cursor_rows(oracle, wm, rows):
    """Concatenated pages == the frozen full result at the cursor's
    watermark: nothing skipped, nothing duplicated, nothing reordered."""
    want = QueryEngine(index_from_state(oracle[wm]), AggregateIndex(),
                       now=NOW).find_by_glob("/fs/*")
    got = np.asarray(rows, object) if rows else \
        np.empty(0, want.dtype)
    assert np.array_equal(got, want), f"cursor rows diverged at wm={wm}"


# ---------------------------------------------------------------------------
# deterministic mutation-class coverage (the sweep's directed cousins)
# ---------------------------------------------------------------------------

def test_snapshot_survives_growth_compact_restore():
    """One snapshot across the three wholesale-rebind mutation classes:
    capacity growth (arena realloc), compaction (slot renumbering), and
    checkpoint restore (state replacement)."""
    primary = PrimaryIndex()
    for i in range(10):
        primary.upsert(f"/a{i}", {"size": float(i), "mtime": 1.0},
                       version=i + 1)
    blob = primary.state_dict()
    snap = primary.snapshot()
    exp = frozen_live(primary)

    paths = [f"/grow{i}" for i in range(5000)]          # forces realloc
    primary.upsert_batch(
        paths, {"size": np.arange(5000.0), "mtime": np.ones(5000)},
        versions=np.full(5000, 100, np.int64))
    check_snap(snap, exp, "growth")

    for i in range(0, 10, 2):
        primary.delete(f"/a{i}", version=200 + i)
    primary.compact()                                   # renumbers slots
    check_snap(snap, exp, "compact")
    assert snap.lookup("/a1") is not None
    assert snap.lookup("/a0") is not None               # pinned pre-delete
    assert primary.lookup("/a0") is None

    primary.load_state(blob)                            # wholesale replace
    check_snap(snap, exp, "restore")
    snap.close()
    assert primary.snapshot_stats() == {"open_snapshots": 0,
                                        "pinned_epochs": 0}


def test_multiple_snapshots_pin_distinct_versions():
    """Snapshots taken at different points each keep their own world;
    epochs pin independently and release independently."""
    primary = td.make_primary(4)
    views = []
    for gen in range(4):
        for i in range(6):
            primary.upsert(f"/g{gen}/f{i}",
                           {"size": float(gen * 10 + i), "mtime": 1.0},
                           version=gen * 10 + i + 1)
        views.append((primary.snapshot(), frozen_live(primary)))
    assert [len(v[1]["path"]) for v in views] == [6, 12, 18, 24]
    for s, exp in reversed(views):
        check_snap(s, exp, "multi-gen")
    for s, _ in views:
        s.close()
    assert primary.snapshot_stats() == {"open_snapshots": 0,
                                        "pinned_epochs": 0}
