"""``QueryEngine.find``: GNU find's ``-name NAME -size SIZEc -newer T``
(IO500's find phase) over the index, against a brute force over
``live()`` on every route (fused kernel, scan, discovery fresh and
stale), on monolithic and 4-shard indexes; through ``QueryService`` and
its result cache; the stage spans and candidate counts of its kernel
route; and the basename prefilter that route runs on the device."""
import fnmatch
import time

import jax
import numpy as np
import pytest

from repro.core import discovery as disc
from repro.core.index import AggregateIndex, PrimaryIndex
from repro.core.metadata import files_only, synth_filesystem
from repro.core.query import QueryEngine
from repro.core.query_service import QueryService
from repro.core.sharded_index import ShardedPrimaryIndex
from repro.core.telemetry import Telemetry

STAMP = 1.7e9
#: an IO500 data directory named by its start time: "01" in a directory
#: name, which a whole-path glob "*01*" would match on every file
DATADIR = "/io500/datafiles/2026.10.18-23.01.05"
LAYOUTS = {"mono": PrimaryIndex, "sharded4": lambda: ShardedPrimaryIndex(4)}
ROUTES = ["kernel", "scan", "discovery", "stale"]


def mdtest_namespace(n_ranks=12, easy=30, hard=130, seed=0):
    """mdtest-shaped records: empty easy files in one directory per
    rank, 3,901-byte hard files in one shared directory, named
    ``file.mdtest.<rank>.<item>``, every mtime after ``STAMP``."""
    rng = np.random.default_rng(seed)
    paths = [f"{DATADIR}/mdtest-easy/test-dir.0-0/mdtest_tree.{r}.0/"
             f"file.mdtest.{r}.{i}" for r in range(n_ranks)
             for i in range(easy)]
    paths += [f"{DATADIR}/mdtest-hard/test-dir.0-0/mdtest_tree.0/"
              f"file.mdtest.{r}.{i}" for r in range(n_ranks)
              for i in range(hard)]
    n_easy = n_ranks * easy
    n = len(paths)
    size = np.where(np.arange(n) < n_easy, 0.0, 3901.0).astype(np.float32)
    mtime = (STAMP + 300 + rng.uniform(0, 900, n)).astype(np.float32)
    fields = {"size": size, "mtime": mtime, "atime": mtime, "ctime": mtime,
              "uid": np.full(n, 1000, np.int32),
              "gid": np.full(n, 1000, np.int32),
              "mode": np.full(n, 0o644, np.int32)}
    order = rng.permutation(n)
    return (np.asarray(paths, object)[order],
            {k: v[order] for k, v in fields.items()})


def build(layout, route, paths, fields):
    idx = LAYOUTS[layout]()
    n = len(paths)
    half = n // 2
    idx.upsert_batch(paths[:half], {k: v[:half] for k, v in fields.items()},
                     np.ones(half, np.int64))
    if route in ("discovery", "stale"):
        idx.attach_discovery()
    # the rest arrives after the attach: the discovery route's delta
    idx.upsert_batch(paths[half:], {k: v[half:] for k, v in fields.items()},
                     np.ones(n - half, np.int64))
    if route == "stale":
        for d in disc.discovery_shards(idx):
            d.invalidate()
    return QueryEngine(idx, AggregateIndex(), now=STAMP + 3600,
                       use_kernels=route != "scan")


def brute(primary, name, size, newer):
    """The semantics written out per record over ``live()``."""
    live = primary.live()
    keep = [fnmatch.fnmatchcase(p.rsplit("/", 1)[-1], name)
            and np.float32(s) == np.float32(size)
            and np.float32(m) > np.float32(newer)
            for p, s, m in zip(live["path"], live["size"], live["mtime"])]
    return live["path"][np.asarray(keep, bool)]


FINDS = [("*01*", 3901, STAMP), ("*01*", 3901, STAMP + 700),
         ("file.mdtest.1?.*", 3901, STAMP), ("*", 0, STAMP),
         ("*[!0-9]3", 3901, STAMP + 900), ("*01*", 3900, STAMP)]


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
@pytest.mark.parametrize("route", ROUTES)
def test_find_equals_brute_force_on_every_route(layout, route):
    paths, fields = mdtest_namespace()
    q = build(layout, route, paths, fields)
    want_route = {"stale": "kernel"}.get(route, route)
    for name, size, newer in FINDS:
        got = q.find(name, size, newer)
        want = brute(q.primary, name, size, newer)
        assert got.dtype == want.dtype and list(got) == list(want), \
            (name, size, newer)
        plan = q.last_plan
        assert plan["query"] == "find" and plan["route"] == want_route
        assert plan["matched"] == len(got) <= plan["verified"]
    assert len(q.find("*01*", 3901, STAMP)) > 0


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_find_on_a_synthetic_filesystem(layout):
    fs = files_only(synth_filesystem(5000, seed=9))
    a, b = LAYOUTS[layout](), LAYOUTS[layout]()
    a.ingest_table(fs, 1)
    b.ingest_table(fs, 1)
    qk = QueryEngine(a, AggregateIndex(), now=STAMP)
    qs = QueryEngine(b, AggregateIndex(), now=STAMP, use_kernels=False)
    size = float(np.float32(fs.size[17]))
    newer = float(np.median(fs.mtime))
    for name in ("*1*", "f1?", "*"):
        got = qk.find(name, size, fs.mtime.min() - 1)
        assert qk.last_plan["route"] == "kernel"
        assert list(got) == list(qs.find(name, size, fs.mtime.min() - 1))
        assert list(got) == list(brute(a, name, size, fs.mtime.min() - 1))
        got = qk.find(name, 0.0, newer)
        assert list(got) == list(brute(a, name, 0.0, newer))


@pytest.mark.parametrize("route", ROUTES)
def test_basename_only_and_float32_edges(route):
    """A directory named with "01" does not make its files match; size
    compares as float32 (2^24 + 1 rounds to 2^24; 3,901.5 is not
    3,901); an mtime equal to the threshold is not newer."""
    t = np.float32(STAMP + 1000)
    recs = [  # path, size, mtime
        (f"{DATADIR}/x/file.mdtest.3.7", 3901.0, t),        # dir "01"
        (f"{DATADIR}/x/file.mdtest.3.101", 3901.0, t),      # name "01"
        ("/fs/d01/file.mdtest.3.1011", 3901.0, t),
        ("/fs/a/file.01", 3901.5, t),
        ("/fs/a/big.01", 2.0 ** 24, t),
        ("/fs/a/big.01.b", 2.0 ** 24 + 2, t),
        ("/fs/a/same.01", 3901.0, np.float32(STAMP)),        # == newer
        ("/fs/a/after.01", 3901.0, np.nextafter(np.float32(STAMP),
                                                np.float32(np.inf))),
        ("top01", 3901.0, t),                                # no "/"
    ]
    paths = np.asarray([r[0] for r in recs], object)
    f32 = np.float32
    fields = {"size": np.asarray([r[1] for r in recs], f32),
              "mtime": np.asarray([r[2] for r in recs], f32)}
    for layout in sorted(LAYOUTS):
        q = build(layout, route, paths, fields)

        def names(*a):
            got = q.find(*a)
            assert list(got) == list(brute(q.primary, *a)), a
            return sorted(p.rsplit("/", 1)[-1] for p in got)
        assert names("*01*", 3901, STAMP) == [
            "after.01", "file.mdtest.3.101", "file.mdtest.3.1011", "top01"]
        assert names("*01*", 2 ** 24 + 1, STAMP) == ["big.01"]
        assert names("*01*", 2 ** 24 + 2, STAMP) == ["big.01.b"]
        assert names("*01*", 3901.5, STAMP) == ["file.01"]
        assert names("*01*", 3901, float(t)) == []
        assert names("*", 3901, STAMP - 1000) == sorted(
            ["file.mdtest.3.7", "file.mdtest.3.101", "file.mdtest.3.1011",
             "same.01", "after.01", "top01"])
        with pytest.raises(ValueError):
            q.find("*", float("inf"), STAMP)


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_service_find_caches_by_arguments(layout):
    paths, fields = mdtest_namespace(seed=3)
    idx = LAYOUTS[layout]()
    idx.upsert_batch(paths, fields, np.ones(len(paths), np.int64))
    svc = QueryService(idx, now=STAMP + 3600)
    want = brute(idx, "*01*", 3901, STAMP)
    a = svc.query("find", "*01*", 3901, STAMP)
    b = svc.query("find", "*01*", 3901, STAMP - 1)       # distinct key
    c = svc.query("find", "*01*", 3901, STAMP)           # repeat: a hit
    assert [r["freshness"]["cached"] for r in (a, b, c)] == [
        False, False, True]
    for r in (a, b, c):
        assert list(r["result"]) == list(want)
    assert svc.cache.stats["hits"] == 1 and svc.cache.stats["misses"] == 2
    # the dashboard entry point dispatches it like any non-predicate query
    (d,) = svc.query_batch([("find", "*01*", 3901, STAMP - 2)])
    assert list(d["result"]) == list(want)
    svc.close()


FIND_SPANS = ("query.arena.pack", "query.select.device",
              "query.select.unpack", "query.select.verify",
              "query.find.name")


def test_find_spans_are_leaves_and_counts_shrink():
    """Every stage span of the kernel route and the name match is
    recorded, none opens inside another, their sum stays within the
    elapsed time, and the candidate counts fall from the kernel's
    bitmap to the verify to the name match."""
    paths, fields = mdtest_namespace(seed=5)
    tel = Telemetry()
    idx = ShardedPrimaryIndex(4, telemetry=tel)
    idx.upsert_batch(paths, fields, np.ones(len(paths), np.int64))
    q = QueryEngine(idx, AggregateIndex(), now=STAMP, telemetry=tel)
    open_, nested = [], []

    def recorder(label):
        class Ann:
            def __enter__(self):
                if open_:
                    nested.append((open_[-1], label))
                open_.append(label)

            def __exit__(self, *exc):
                open_.pop()
        return Ann()
    for attr in ("_span_pack", "_span_device", "_span_unpack",
                 "_span_verify", "_span_name"):
        getattr(q, attr)._annotation = recorder
    t0 = time.perf_counter()
    got = q.find("*01*", 3901, STAMP)
    elapsed = time.perf_counter() - t0
    assert len(got) > 0 and not nested
    snap = tel.snapshot(traces=False)["metrics"]
    spans = {s["labels"]["span"]: s["value"]
             for s in snap["span_seconds_total"]["series"]
             if s["labels"]["span"].startswith("query.")}
    assert set(spans) == set(FIND_SPANS)
    assert all(v > 0 for v in spans.values())
    assert sum(spans.values()) <= elapsed
    counts = {s["labels"]["stage"]: s["value"]
              for s in snap["query_candidates_total"]["series"]
              if s["labels"]["query"] == "find"}
    assert counts["kernel"] >= counts["name_device"] \
        >= counts["verified"] >= counts["matched"]
    assert counts["name_device"] == q.last_plan["name_device"]
    assert counts["matched"] == len(got)
    assert counts["verified"] == q.last_plan["verified"]
    assert counts["kernel"] == q.last_plan["candidates"]


#: basenames beside mdtest's that the prefilter has to get right: a
#: literal at a basename's first and last byte, one that only a
#: directory holds, a basename longer than the column (kept by its
#: flag) and non-ASCII ones (substrings of strict UTF-8); the sharded
#: slot map holds no surrogate escape, so the kernel-level test below
#: covers that flag
ODD_NAMES = ["ax01", "bxc", "a.c", "abc", "01file", "data.h5", "x.h5.bak",
             "file.mdtest.0.01" + "q" * 30, "résumé01.h5", "数据01.h5",
             "日本語のファイル名01", "c"]
PREFILTER_CASES = [
    ("*01*", "device"), ("file.*", "device"), ("*.h5", "device"),
    ("?x*", "device"), ("[ab]*c", "device"), ("*", "host"),
    ("file.mdtest.1*", "device"),      # a literal at the first byte
    ("*.mdtest.2.7", "device"),        # and at the last byte
    ("*09.01*", "device"),             # only in a directory name
    ("*数据*", "device"), ("*é*", "device"), ("*qqqq*", "device"),
]


def odd_namespace():
    paths, fields = mdtest_namespace(n_ranks=4, easy=10, hard=40, seed=11)
    extra = [f"/io500/2024.11.04-09.01.37/odd/{b}" for b in ODD_NAMES]
    n = len(extra)
    t = np.float32(STAMP + 500)
    more = {k: np.full(n, v[0], v.dtype) for k, v in fields.items()}
    more.update(size=np.full(n, 3901.0, np.float32),
                mtime=np.full(n, t, np.float32))
    return (np.concatenate([paths, np.asarray(extra, object)]),
            {k: np.concatenate([v, more[k]]) for k, v in fields.items()})


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
@pytest.mark.parametrize("name,route", PREFILTER_CASES)
def test_prefilter_route_equals_brute_force(layout, name, route):
    """The kernel route with the basename prefilter returns the brute
    force's answer in scan order; a glob with no literal leaves the
    match to the host."""
    paths, fields = odd_namespace()
    tel = Telemetry()
    idx = LAYOUTS[layout]()
    idx.upsert_batch(paths, fields, np.ones(len(paths), np.int64))
    q = QueryEngine(idx, AggregateIndex(), now=STAMP, telemetry=tel)
    got = q.find(name, 3901, STAMP)
    want = brute(idx, name, 3901, STAMP)
    assert list(got) == list(want)
    plan = q.last_plan
    assert plan["route"] == "kernel" and plan["name_prefilter"] == route
    snap = tel.snapshot(traces=False)["metrics"]
    routes = {s["labels"]["route"]: s["value"] for s in
              snap["query_name_prefilter_total"]["series"]}
    assert routes == {route: 1}
    if route == "device":
        assert plan["name_keep"] == 1     # the long basename
        assert plan["candidates"] >= plan["name_device"] \
            >= plan["verified"] >= plan["matched"]
    if name == "*09.01*":
        # only the flagged row passes the device test
        assert len(want) == 0 and plan["name_device"] == 1
    if name in ("*01*", "*数据*", "*é*", "*qqqq*"):
        assert len(want) > 0


def test_prefilter_words_equal_a_numpy_reference():
    """The packed prefilter words over a random column are, bit for bit
    in ``unpack_bits`` order, ``all(literal in basename) or flag``;
    after the AND the arena's padding rows are clear."""
    from repro.kernels.predeval import names as pk_names
    from repro.kernels.predeval import ref as pk_ref
    rng = np.random.default_rng(4)
    alphabet = list("ab01./") + ["é", "数"]
    n, n_pad = 3000, 4096
    base = ["".join(rng.choice(alphabet, rng.integers(0, 40)))
            .replace("/", "") for _ in range(n)]
    base[:3] = ["caf\udcffab", "ab" * 17, ""]
    paths = np.asarray([f"/d{i % 7}ab/{b}" for i, b in enumerate(base)],
                       object)
    col = pk_names.pack_names(paths, n, n_pad)
    enc = [b.encode("utf-8", "surrogatepass") for b in base]
    flag = np.asarray([len(e) > pk_names.NAME_BYTES or "\udcff" in b
                       for b, e in zip(base, enc)])
    assert col.n_keep == flag.sum() >= 2
    pred = np.zeros((1, n_pad), bool)
    pred[0, :n] = True                 # the padding rows are dead
    dev = jax.numpy.asarray(pk_ref.pack_words(pred))
    for lits in (["01"], ["ab", "0"], ["a.b"], ["é"], ["数0", "b"]):
        want = np.asarray([all(s.encode() in e for s in lits) or f
                           for e, f in zip(enc, flag)])
        op = pk_names.name_literals(lits)
        words = np.asarray(jax.jit(pk_names.prefilter_words)(
            col.names, col.keep, *op))
        assert np.array_equal(pk_ref.unpack_bits(words, n), want), lits
        anded, kept = pk_names.and_row(dev, 0, col, op)
        bits = pk_ref.unpack_bits(np.asarray(anded)[0], n_pad)
        assert np.array_equal(bits[:n], want) and not bits[n:].any()
        assert int(kept) == n
    # a path holding NUL leaves no byte boundary to trust: all kept
    col = pk_names.pack_names(np.asarray(["/a/b\0c", "/d/e"], object), 2, 32)
    assert col.n_keep == 2


def test_new_timestamps_and_patterns_of_one_bucket_do_not_compile():
    """Two finds at new timestamps, and two patterns whose literals fall
    in one (count, length) bucket, reuse the compiled passes."""
    from repro.kernels.predeval import names as pk_names
    from repro.kernels.predeval import ops as pk_ops
    paths, fields = mdtest_namespace(seed=6)
    idx = ShardedPrimaryIndex(4)
    idx.upsert_batch(paths, fields, np.ones(len(paths), np.int64))
    q = QueryEngine(idx, AggregateIndex(), now=STAMP)
    q.find("*01*", 3901, STAMP)
    sizes = (pk_names._and_row._cache_size(),
             pk_ops._jitted(False, False)._cache_size())
    for name, newer in (("*01*", STAMP - 1), ("*01*", STAMP - 2),
                        ("*23*", STAMP), ("*7.1*", STAMP - 3)):
        got = q.find(name, 3901, newer)
        assert list(got) == list(brute(idx, name, 3901, newer))
        assert q.last_plan["name_prefilter"] == "device"
    assert (pk_names._and_row._cache_size(),
            pk_ops._jitted(False, False)._cache_size()) == sizes


def test_predicate_queries_build_no_name_column():
    """``_kernel_select`` and ``select_many`` pack and cache the arenas
    alone; the find then reuses those arenas and adds the names."""
    paths, fields = mdtest_namespace(seed=7)
    tel = Telemetry()
    idx = ShardedPrimaryIndex(4, telemetry=tel)
    idx.upsert_batch(paths, fields, np.ones(len(paths), np.int64))
    q = QueryEngine(idx, AggregateIndex(), now=STAMP + 3600, telemetry=tel)
    q.world_writable()
    assert q.last_plan["route"] == "kernel"
    assert "name_prefilter" not in q.last_plan
    q.select_many([("past_retention", (60,), {}),
                   ("large_cold_files", (100, 60), {})])
    assert q.last_plan["route"] == "kernel" and not q._names_cache
    arenas = {si: hit for si, hit in q._arena_cache.items()}
    assert len(arenas) == 4
    q.find("*01*", 3901, STAMP)
    assert q._arena_cache == arenas and len(q._names_cache) == 4
    for si, (key, col) in q._names_cache.items():
        assert key == arenas[si][0] and col.n_pad == arenas[si][1].n_pad
    snap = tel.snapshot(traces=False)["metrics"]
    assert "name_device" not in {
        s["labels"]["stage"] for s in snap["query_candidates_total"]["series"]
        if s["labels"]["query"] != "find"}


@pytest.mark.parametrize("route", ["scan", "discovery"])
def test_other_routes_leave_the_match_to_the_host(route):
    paths, fields = mdtest_namespace(seed=8)
    tel = Telemetry()
    idx = ShardedPrimaryIndex(4, telemetry=tel)
    idx.upsert_batch(paths, fields, np.ones(len(paths), np.int64))
    if route == "discovery":
        idx.attach_discovery()
    q = QueryEngine(idx, AggregateIndex(), now=STAMP, telemetry=tel,
                    use_kernels=route != "scan")
    got = q.find("*01*", 3901, STAMP)
    assert list(got) == list(brute(idx, "*01*", 3901, STAMP))
    assert q.last_plan["route"] == route
    assert q.last_plan["name_prefilter"] == "host" and not q._names_cache
    snap = tel.snapshot(traces=False)["metrics"]
    assert [(s["labels"]["route"], s["value"]) for s in
            snap["query_name_prefilter_total"]["series"]] == [("host", 1)]
