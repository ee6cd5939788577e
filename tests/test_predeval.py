"""Differential suite for the fused predicate kernel (DESIGN.md §13).

Pins the three-way bit-identity (Pallas kernel / jitted jnp oracle /
numpy host oracle) on the packed bitmaps, the candidate-superset
property, and — through the engine — byte-identity with the numpy scan
across layouts and batching."""
import functools

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.discovery import eval_pred
from repro.core.index import AggregateIndex, PrimaryIndex
from repro.core.metadata import files_only, synth_filesystem
from repro.core.query import QueryEngine, pred_spec
from repro.core.sharded_index import ShardedPrimaryIndex
from repro.kernels.predeval import ops as pk_ops
from repro.kernels.predeval import ref as pk_ref

NOW = 1.7e9


def synth_columns(n, seed=0, alive_frac=0.9):
    rng = np.random.default_rng(seed)
    cols = {
        "size": rng.lognormal(9, 2.5, n).astype(np.float32),
        "atime": (NOW - rng.uniform(0, 4e7, n)).astype(np.float32),
        "mtime": (NOW - rng.uniform(0, 8e7, n)).astype(np.float32),
        "uid": rng.integers(0, 64, n).astype(np.int32),
        "gid": rng.integers(0, 8, n).astype(np.int32),
        "mode": rng.choice([0o644, 0o600, 0o777, 0o666], n).astype(np.int32),
    }
    alive = (rng.random(n) < alive_frac).astype(np.int32)
    return cols, alive


PRED_LISTS = [
    [("mode", "mask", 0o002)],
    [("atime", "lt", NOW - 180 * 86400)],
    [("size", "gt", 1e5), ("atime", "lt", NOW - 120 * 86400)],
    [("uid", "notin", list(range(20)))],
    [("mtime", "lt", NOW - 2 * 365 * 86400)],
    [("size", "gt", 1e3), ("size", "lt", 1e7)],       # merged range
    [("uid", "gt", 10), ("uid", "lt", 50)],           # int range
]


def eval_words(cols, alive, progs):
    """(host words, jnp-route words, pallas-interpret words)."""
    n = len(alive)
    arena = pk_ops.pack_arena(cols, alive, n)
    w_route = pk_ops.predeval_words(arena, progs)
    w_host = pk_ref.predeval_host(np.asarray(arena.fcols),
                                  np.asarray(arena.icols),
                                  np.asarray(arena.alive), progs)
    import jax.numpy as jnp

    from repro.kernels.predeval.predeval import predeval
    w_pl = np.asarray(predeval(
        arena.fcols, arena.icols, arena.alive, jnp.asarray(progs.ops),
        jnp.asarray(progs.lo), jnp.asarray(progs.hi),
        jnp.asarray(progs.msk), jnp.asarray(progs.setrows),
        jnp.asarray(progs.setcol), jnp.asarray(progs.setvals),
        has_set=progs.has_set, interpret=True))
    return w_host, w_route, w_pl


# ---------------------------------------------------------------------------
# program compilation
# ---------------------------------------------------------------------------

def test_compile_range_merges_and_widens():
    p = pk_ref.compile_program([("size", "gt", 100.0),
                                ("size", "lt", 1e6),
                                ("size", "gt", 200.0)])
    ci = pk_ref.COL_INDEX["size"]
    assert p["ops"][ci] == pk_ref.OP_RANGE
    # widened one ulp outward around the tightest bounds
    assert p["lo"][ci] == np.nextafter(np.float32(200.0),
                                       np.float32(-np.inf))
    assert p["hi"][ci] == np.nextafter(np.float32(1e6), np.float32(np.inf))


def test_compile_int_range_uses_integer_neighbour():
    p = pk_ref.compile_program([("uid", "gt", 10), ("uid", "lt", 20.5)])
    ci = pk_ref.COL_INDEX["uid"]
    assert p["lo"][ci] == np.float32(11)
    assert p["hi"][ci] == np.float32(20)


def test_compile_inexpressible_cases():
    assert pk_ref.compile_program([("ctime", "lt", 1.0)]) is None
    assert pk_ref.compile_program([("size", "mask", 2)]) is None
    assert pk_ref.compile_program([("mode", "mask", 2),
                                   ("mode", "mask", 4)]) is None
    assert pk_ref.compile_program(
        [("uid", "notin", list(range(pk_ref.SET_CAP + 1)))]) is None
    assert pk_ref.compile_program(
        [("uid", "notin", [1]), ("gid", "notin", [2])]) is None
    assert pk_ref.compile_program([("size", "between", (1, 2))]) is None


def test_compile_notin_drops_out_of_int32_and_empty():
    # out-of-int32 values can never equal a stored int32
    p = pk_ref.compile_program([("uid", "notin", [5, 2**40])])
    assert p["set"][1].tolist() == [5]
    # notin {} matches everything -> no-op, not a set program
    p = pk_ref.compile_program([("uid", "notin", [])])
    assert p["set"] is None
    assert p["ops"][pk_ref.COL_INDEX["uid"]] == pk_ref.OP_NONE


def test_stack_programs_pads_and_sorts_sets():
    progs = pk_ref.stack_programs(
        [pk_ref.compile_program(p) for p in PRED_LISTS[:5]])
    assert progs.k == 5 and progs.k_pad == 8
    assert progs.has_set
    sv = progs.setvals[0]
    assert np.all(np.diff(sv) >= 0)            # sorted, max-padded
    assert sv[-1] == sv.max()


# ---------------------------------------------------------------------------
# three-way bit-identity + superset
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [100, 4096, 10_000])
def test_three_way_bit_identity(n):
    cols, alive = synth_columns(n, seed=n)
    progs = pk_ref.stack_programs(
        [pk_ref.compile_program(p) for p in PRED_LISTS])
    w_host, w_route, w_pl = eval_words(cols, alive, progs)
    assert np.array_equal(w_host, w_route)
    assert np.array_equal(w_host, w_pl)


def test_bitmap_is_exact_superset_of_scan_matches():
    n = 10_000
    cols, alive = synth_columns(n, seed=7)
    progs = pk_ref.stack_programs(
        [pk_ref.compile_program(p) for p in PRED_LISTS])
    arena = pk_ops.pack_arena(cols, alive, n)
    words = pk_ops.predeval_words(arena, progs)
    for k, preds in enumerate(PRED_LISTS):
        cand = pk_ops.bitmap_slots(words, k, n)
        exact = alive.astype(bool).copy()
        for col, op, arg in preds:
            exact &= eval_pred(cols[col], op, arg)
        exact_slots = np.flatnonzero(exact)
        assert np.isin(exact_slots, cand).all(), (k, "candidate miss")
        # padding rows never leak
        assert len(cand) == 0 or cand[-1] < n


def test_dead_rows_never_match():
    n = 512
    cols, alive = synth_columns(n, seed=3, alive_frac=0.0)
    progs = pk_ref.stack_programs(
        [pk_ref.compile_program([("size", "gt", -1.0)])])
    arena = pk_ops.pack_arena(cols, alive, n)
    words = pk_ops.predeval_words(arena, progs)
    assert not words.any()


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 10_000), n=st.integers(1, 700),
       pseed=st.integers(0, 10_000))
def test_property_random_programs(seed, n, pseed):
    """Random predicate programs over random arenas: every compiled
    program's bitmap equals the host oracle's bit-for-bit and is an
    exact superset of the scan matches."""
    cols, alive = synth_columns(n, seed=seed, alive_frac=0.8)
    rng = np.random.default_rng(pseed)
    preds = []
    for _ in range(int(rng.integers(1, 5))):
        col = pk_ref.PRED_COLUMNS[int(rng.integers(6))]
        if col in ("uid", "gid", "mode"):
            op = ["lt", "gt", "mask", "notin"][int(rng.integers(4))]
        else:
            op = ["lt", "gt"][int(rng.integers(2))]
        if op in ("lt", "gt"):
            lo, hi = ((0.0, 1e8) if col in ("uid", "gid", "mode")
                      else (1.0, NOW))
            arg = float(rng.uniform(lo, hi))
        elif op == "mask":
            arg = int(rng.integers(1, 0o1000))
        else:
            arg = rng.integers(-5, 71, int(rng.integers(0, 11))).tolist()
        preds.append((col, op, arg))
    prog = pk_ref.compile_program(preds)
    if prog is None:                   # conflicting ops etc. -> scan
        return
    progs = pk_ref.stack_programs([prog])
    arena = pk_ops.pack_arena(cols, alive, n)
    words = pk_ops.predeval_words(arena, progs)
    w_host = pk_ref.predeval_host(np.asarray(arena.fcols),
                                  np.asarray(arena.icols),
                                  np.asarray(arena.alive), progs)
    assert np.array_equal(words, w_host)
    cand = pk_ops.bitmap_slots(words, 0, n)
    exact = alive.astype(bool).copy()
    for col, op, arg in preds:
        exact &= eval_pred(cols[col], op, arg)
    assert np.isin(np.flatnonzero(exact), cand).all()


# ---------------------------------------------------------------------------
# engine integration: route + byte-identity with the scan
# ---------------------------------------------------------------------------

LAYOUTS = {"mono": lambda: PrimaryIndex(),
           "sharded4": lambda: ShardedPrimaryIndex(4)}

MIX = [
    ("world_writable", (), {}),
    ("not_accessed_since", (180 * 86400,), {}),
    ("large_cold_files", (1e6, 90 * 86400), {}),
    ("owned_by_deleted_users", (list(range(8)),), {}),
    ("past_retention", (365 * 86400,), {}),
]


def make_engines(layout, n_files=6000, seed=1):
    fs = files_only(synth_filesystem(n_files, seed=seed))
    a, b = LAYOUTS[layout](), LAYOUTS[layout]()
    a.ingest_table(fs, 1)
    b.ingest_table(fs, 1)
    return (QueryEngine(a, AggregateIndex(), now=NOW),
            QueryEngine(b, AggregateIndex(), now=NOW, use_kernels=False))


#: the Table-I suite at its selective parameters (2-year cutoffs, an
#: orphan sweep where all but the 4 rarest owners are active)
SELECTIVE = {
    "not_accessed_since": ((365 * 86400,), {}),
    "large_cold_files": ((1e7, 180 * 86400), {}),
    "past_retention": ((2 * 365 * 86400,), {}),
    "owned_by_deleted_users": ((list(range(28)),), {}),
}


def dashboard_mix():
    """32 panels: the four parametrised Table-I families swept over 7
    thresholds each, plus 4 world-writable panels (DESIGN.md §13.4)."""
    mix = []
    for v in range(7):
        months = (3 + 2 * v) * 30 * 86400
        mix += [
            ("not_accessed_since", (months,), {}),
            ("large_cold_files", (10.0 ** (6 + v / 2), months), {}),
            ("past_retention", (2 * months,), {}),
            ("owned_by_deleted_users", (list(range(4 + 4 * v)),), {}),
        ]
    return mix + [("world_writable", (), {})] * 4


@functools.lru_cache(maxsize=None)
def shared_engines(layout, seed):
    """Read-only (kernel, scan) engine pairs shared across cases."""
    return make_engines(layout, seed=seed)


@pytest.mark.parametrize("layout,query", [
    *(pytest.param(layout, None, id=layout) for layout in LAYOUTS),
    *(pytest.param(layout, query, id=f"{layout}-{query}")
      for layout in LAYOUTS for query in SELECTIVE)])
def test_engine_kernel_route_byte_identical(layout, query):
    """The Table-I mix (``query`` None), then each query at its
    selective parameters, takes the kernel route and byte-matches the
    scan."""
    qk, qs = shared_engines(layout, 1)
    calls = MIX if query is None else [(query, *SELECTIVE[query])]
    for name, args, kw in calls:
        a = getattr(qk, name)(*args, **kw)
        assert qk.last_plan["route"] == "kernel", (name, qk.last_plan)
        b = getattr(qs, name)(*args, **kw)
        assert qs.last_plan["route"] == "scan"
        assert a.dtype == b.dtype and np.array_equal(a, b), (name, args)


@pytest.mark.parametrize("layout,mix", [
    *(pytest.param(layout, "table1", id=layout) for layout in LAYOUTS),
    *(pytest.param(layout, "dashboard32", id=f"{layout}-dashboard32")
      for layout in LAYOUTS)])
def test_select_many_matches_individual(layout, mix):
    qk, qs = shared_engines(layout, 2)
    if mix == "table1":
        specs = MIX
        batch = qk.select_many(MIX + [("find_by_name", (r"/f1\d$",), {})])
        assert qk.last_plan["query"] in ("select_many", "find_by_name")
        # the non-predicate tail entry dispatched normally
        assert np.array_equal(batch[-1], qs.find_by_name(r"/f1\d$"))
    else:
        specs = dashboard_mix()
        batch = qk.select_many(specs)
        # every panel joined the one stacked pass
        assert (qk.last_plan["batched"], qk.last_plan["fallback"]) == \
            (len(specs), 0)
    for (name, args, kw), res in zip(specs, batch):
        ref = getattr(qs, name)(*args, **kw)
        assert res.dtype == ref.dtype and np.array_equal(res, ref), \
            (name, args)


def test_select_many_pins_one_clock():
    """Time-relative members of a batch all resolve the same now."""
    idx = PrimaryIndex()
    idx.upsert_batch(
        ["/fs/x"], {"path_hash": np.array([1], np.uint32),
                    "atime": np.array([999.0], np.float32)},
        np.array([1], np.int64))
    clock = iter([2000.0, 3000.0])
    q = QueryEngine(idx, AggregateIndex(), now=lambda: next(clock))
    r = q.select_many([("not_accessed_since", (1500.0,), {}),
                       ("not_accessed_since", (1500.0,), {})])
    # both see now=2000 (cutoff 500 < atime 999): no match. Had the
    # second spec resolved now=3000 (cutoff 1500) it would match.
    assert list(r[0]) == list(r[1]) == []


def test_kernel_route_respects_discovery_freshness():
    """Route order: fresh discovery wins; stale discovery falls back to
    the kernel (not the scan) when kernels are on."""
    fs = files_only(synth_filesystem(2000, seed=5))
    idx = PrimaryIndex()
    idx.ingest_table(fs, 1)
    idx.attach_discovery()
    q = QueryEngine(idx, AggregateIndex(), now=NOW)
    q.world_writable()
    assert q.last_plan["route"] == "discovery"
    idx.ingest_table(fs, 2)                   # bulk ingest -> stale
    got = q.world_writable()
    assert q.last_plan["route"] == "kernel"
    qs = QueryEngine(idx, AggregateIndex(), now=NOW, use_kernels=False)
    assert np.array_equal(got, qs.world_writable())
    idx.rebuild_discovery()
    q.world_writable()
    assert q.last_plan["route"] == "discovery"


def test_engine_arena_cache_tracks_epochs():
    fs = files_only(synth_filesystem(1000, seed=6))
    idx = PrimaryIndex()
    idx.ingest_table(fs, 1)
    q = QueryEngine(idx, AggregateIndex(), now=NOW)
    q.world_writable()
    (key1, arena1), = q._arena_cache.values()
    q.past_retention(365 * 86400)
    (key2, arena2), = q._arena_cache.values()
    assert key2 == key1 and arena2 is arena1   # cache hit, same epoch
    idx.delete_batch([fs.paths[0]], np.array([2], np.int64))
    q.world_writable()
    (key3, _), = q._arena_cache.values()
    assert key3 != key1                        # mutation invalidates


def test_pack_matrix_weights():
    """The predeval bit-packing matrix routes row r to word r // 32 at
    weight 2^(r % 16), low half for bits 0..15, high half for 16..31."""
    from repro.kernels.predeval.predeval import WORDS, _pack_matrix
    m = _pack_matrix()
    r, c = np.nonzero(m)
    assert np.array_equal(r, np.arange(m.shape[0]))
    assert np.array_equal(c, r // 32 + WORDS * ((r % 32) >= 16))
    assert np.array_equal(m[r, c], 2.0 ** (r % 16))


# ---------------------------------------------------------------------------
# vectorized zone pruning
# ---------------------------------------------------------------------------

def test_zone_keep_matches_scalar_zone_checks():
    rng = np.random.default_rng(0)
    zlo = np.sort(rng.uniform(0, 1e6, 32))
    zhi = zlo + rng.uniform(0, 1e5, 32)
    zlo = np.append(zlo, np.inf)               # empty-run zone
    zhi = np.append(zhi, -np.inf)
    for op in ("lt", "gt"):
        for arg in (0.0, 123.456, 5e5, 2e6):
            keep = pk_ref.zone_keep(zlo, zhi, op, arg, np.float32)
            for r in range(len(zlo)):
                if op == "lt":
                    scalar = not (zlo[r] > pk_ref.widen_hi(arg, np.float32))
                else:
                    scalar = not (zhi[r] < pk_ref.widen_lo(arg, np.float32))
                assert keep[r] == scalar, (op, arg, r)
    assert pk_ref.zone_keep(zlo, zhi, "mask", 2, np.int32).all()
    assert pk_ref.zone_keep(zlo, zhi, "notin", [1], np.int32).all()


def test_pred_spec_matches_method_semantics():
    specs = {
        ("world_writable", (), ()): [("mode", "mask", 0o002)],
        ("not_accessed_since", (100.0,), ()): [("atime", "lt", NOW - 100.0)],
        ("past_retention", (50.0,), ()): [("mtime", "lt", NOW - 50.0)],
    }
    for (name, args, _), want in specs.items():
        assert pred_spec(name, args, {}, NOW) == want
    got = pred_spec("large_cold_files", (1e6,), {"idle_seconds": 100.0}, NOW)
    assert got == [("size", "gt", 1e6), ("atime", "lt", NOW - 100.0)]
    assert pred_spec("stat", ("/x",), {}, NOW) is None
    assert pred_spec("not_accessed_since", (), {}, NOW) is None  # bad arity
    assert pred_spec("not_accessed_since", (1.0, 2.0), {}, NOW) is None
