"""The IO500 find cell: its namespace generator's shape, its reference
against an independent brute force, a whole run at a tiny size on the
CPU (sound runs correct; a whole-path match, a dropped match, the
kernel route bypassed and the bfloat16 control not correct), its metric
readers and the predicate kernel's byte count, and how the harness
finds the cell."""
import fnmatch
import json
import os
import posixpath
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))

import find_reference  # noqa: E402
import harness  # noqa: E402
import mdtest  # noqa: E402
import roofline_predeval  # noqa: E402
import run  # noqa: E402

CELL = "io500_find_8m.find"
TINY_CFG = {"namespace": {"n_files": 12000, "ranks": 8},
            "index": {"chunk": 1024}}


def _spec(**kw):
    spec = json.load(open(os.path.join(BENCH, "configs",
                                       "io500_find_8m.json")))["namespace"]
    return dict(spec, n_files=12000, ranks=8, **kw)


def test_namespace_has_the_mdtest_shape():
    spec = _spec()
    ns = mdtest.generate(spec, 0)
    assert len(ns) == 12000 and ns.n_hard == 4000 and ns.n_easy == 8000
    assert ns.run_dir == "/io500/datafiles/2024.11.04-09.01.37"
    size = ns.columns["size"]
    assert set(np.unique(size).tolist()) == {0.0, 3901.0}
    dirs = np.asarray([p.rsplit("/", 1)[0] for p in ns.paths], object)
    hard = size == 3901
    assert int(hard.sum()) == 4000
    assert set(dirs[hard]) == {
        ns.run_dir + "/mdtest-hard/test-dir.0-0/mdtest_tree.0"}
    easy_dirs = sorted(set(dirs[~hard]))
    assert easy_dirs == sorted(
        f"{ns.run_dir}/mdtest-easy/test-dir.0-0/mdtest_tree.{r}.0"
        for r in range(8))
    for p, d in zip(ns.paths[:50], dirs[:50]):
        _, rank, item = p.rsplit("/", 1)[1].rsplit(".", 2)
        assert p.rsplit("/", 1)[1] == f"file.mdtest.{rank}.{item}"
        assert d.endswith("mdtest_tree.0") or d.endswith(
            f"mdtest_tree.{rank}.0")
    # items per rank differ by at most one; every file newer than the
    # timestamp, hard files after easy ones (IO500's phase order)
    ranks = np.asarray([int(p.rsplit(".", 2)[1]) for p in ns.paths])
    for on in (hard, ~hard):
        c = np.bincount(ranks[on], minlength=8)
        assert c.max() - c.min() <= 1
    mt = ns.columns["mtime"]
    assert mt.dtype == np.float32 and (mt > np.float32(ns.stamp)).all()
    assert mt[~hard].max() < mt[hard].min()
    assert {k: v.dtype for k, v in ns.columns.items()}["uid"] == np.int32


@pytest.mark.parametrize("seed", [1, 2 ** 31 + 11])
def test_every_seed_does_the_same_work(seed):
    spec = _spec()
    a = mdtest.namespace_for(spec, 0)
    b = mdtest.namespace_for(spec, seed)
    assert list(a.paths) != list(b.paths)
    assert sorted(a.paths) == sorted(b.paths)
    row = {p: i for i, p in enumerate(b.paths)}
    j = np.array([row[p] for p in a.paths])
    for k, v in a.columns.items():
        assert np.array_equal(v, b.columns[k][j]), k
    assert list(mdtest.namespace_for(spec, seed).paths) == list(b.paths)


def _brute(paths, size, mtime, name, size_arg, newer):
    """Record by record, written independently of the reference."""
    out = []
    for p, s, m in zip(paths, size, mtime):
        if (fnmatch.fnmatchcase(posixpath.basename(p), name)
                and float(np.float32(s)) == float(np.float32(size_arg))
                and float(np.float32(m)) > float(np.float32(newer))):
            out.append(p)
    return out


def test_reference_equals_a_brute_force():
    ns = mdtest.namespace_for(_spec(), 5)
    extra = np.asarray(["/a/2024.01/file.mdtest.3.7", "/a/b/file.01",
                        "/a/b/big01", "/a/b/big01.x", "/a/b/same01"], object)
    paths = np.concatenate([ns.paths, extra])
    t = np.float32(ns.stamp + 2000)
    size = np.concatenate([ns.columns["size"], np.asarray(
        [3901, 3901, 2 ** 24, 2 ** 24 + 2, 3901], np.float32)])
    mtime = np.concatenate([ns.columns["mtime"], np.asarray(
        [t, t, t, t, np.float32(ns.stamp)], np.float32)])
    for name, size_arg, newer in [
            ("*01*", 3901, ns.stamp), ("*01*", 3901, ns.stamp - 7),
            ("*01*", 3901, ns.stamp + 1000), ("*01*", 2 ** 24 + 1,
                                              ns.stamp),
            ("file.mdtest.[0-3].1?", 3901, ns.stamp - 3000),
            ("*", 0, ns.stamp)]:
        got = find_reference.Find(paths, size, mtime, name, size_arg)(newer)
        want = _brute(paths, size, mtime, name, size_arg, newer)
        assert list(got) == want, (name, size_arg, newer)
    got = find_reference.Find(paths, size, mtime, "*01*", 3901)(ns.stamp)
    assert "/a/b/file.01" in got and "/a/2024.01/file.mdtest.3.7" not in got
    assert "/a/b/same01" not in got and len(got) > 5


def _go(capsys, control=""):
    rc = run.main(["--workload", CELL, "--seed", "4294967311",
                   "--seconds", "1.5", "--trace", "0"],
                  require_chip=False, control=control,
                  overrides={"cfg": TINY_CFG})
    assert rc == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert set(line) >= {"correct", "attempted", "failed", "metrics",
                         "device", "checks"}
    return line


@pytest.fixture(autouse=True)
def _no_cache(monkeypatch):
    monkeypatch.setattr(harness, "configure_compile_cache", lambda: "")


def test_sound_run_is_correct(capsys):
    line = _go(capsys)
    assert line["correct"], line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 1
    assert {c["name"] for c in line["checks"]} == {
        "find_mismatch", "route_not_kernel", "window_compiles"}
    assert set(line["metrics"]) == {"records_per_s", "setup_s"}


def _fault(kind, monkeypatch):
    from repro.core.query import QueryEngine
    orig = QueryEngine.find
    if kind == "whole_path":
        def find(self, name, size, newer):
            got = orig(self, "*", size, newer)
            return got[[fnmatch.fnmatchcase(p, name) for p in got]]
        monkeypatch.setattr(QueryEngine, "find", find)
    elif kind == "dropped_match":
        monkeypatch.setattr(QueryEngine, "find",
                            lambda self, *a: orig(self, *a)[1:])
    elif kind == "kernel_bypassed":
        monkeypatch.setattr(QueryEngine, "_kernel_select",
                            lambda self, qname, preds: None)


@pytest.mark.parametrize("fault,check", [
    ("whole_path", "find_mismatch"), ("dropped_match", "find_mismatch"),
    ("kernel_bypassed", "route_not_kernel")])
def test_fault_is_caught(fault, check, capsys, monkeypatch):
    _fault(fault, monkeypatch)
    line = _go(capsys)
    assert not line["correct"], line["checks"]
    failed = {c["name"] for c in line["checks"] if c["value"] > c["limit"]}
    assert failed == {check}


def test_bf16_control_is_not_correct(capsys):
    line = _go(capsys, control="bf16")
    assert not line["correct"], line["checks"]
    failed = {c["name"] for c in line["checks"] if c["value"] > c["limit"]}
    assert failed == {"find_mismatch"}


SPAN_METRICS = [
    ("select_device_share.find", "query.select.device"),
    ("select_unpack_share.find", "query.select.unpack"),
    ("select_verify_share.find", "query.select.verify"),
    ("name_match_share.find", "query.find.name"),
]


@pytest.mark.parametrize("metric,span", SPAN_METRICS)
def test_span_share_and_none_without_its_counter(metric, span):
    read = harness.metric_reader(metric)
    key = f"span_seconds_total|span={span}"
    ctx = {"counters": {key: 2.5, "span_seconds_total|span=x": 9.0},
           "spans": {}, "window_s": 20.0}
    assert read(ctx) == pytest.approx(12.5)
    for counters in ({}, {key: 0.0}, {"span_seconds_total|span=x": 1.0}):
        assert read({"counters": counters, "spans": {},
                     "window_s": 20.0}) is None


def test_predeval_bytes_count_real_rows():
    # two tested columns and alive at 4 B, one bit out, per real row
    assert roofline_predeval.predeval_bytes(8, 2) == 8 * 12 + 1
    assert roofline_predeval.predeval_bytes(1 << 21, 2, 3) == \
        (1 << 21) * 12 + 3 * (1 << 18)


def test_roofline_and_idle_readers():
    red = {"kernel_s": {"predeval": 0.001}, "busy_s": 0.5,
           "window_s": 20.0}
    ctx = {"trace": red, "work": {"predeval": 819e9 * 0.0005},
           "device_kind": "TPU v5 lite"}
    assert harness.metric_reader("predeval_roofline.find")(ctx) == \
        pytest.approx(50.0)
    assert harness.metric_reader("device_idle.find")(ctx) == \
        pytest.approx(97.5)
    for none in ({"trace": None, "work": {}},
                 {"trace": dict(red, kernel_s={}), "work": ctx["work"],
                  "device_kind": "TPU v5 lite"}):
        assert harness.metric_reader("predeval_roofline.find")(none) is None
    assert harness.metric_reader("device_idle.find")({"trace": None}) is None


def test_harness_finds_the_cell_and_leaves_the_rescan_as_it_was():
    cell = harness.load_cell(CELL)
    assert cell["cfg"]["kind"] == "find" and cell["chips"] == 1
    assert cell["cfg"]["reduced"] == ["n_files"]
    assert [m["name"] for m in cell["end_to_end"]] == [
        "records_per_s", "setup_s"]
    assert [m["name"] for m in cell["per_layer"]] == [
        "select_device_share.find", "select_unpack_share.find",
        "select_verify_share.find", "name_match_share.find",
        "predeval_roofline.find", "device_idle.find"]
    scan = harness.load_cell("scan_refresh_4m.rescan")
    assert [m["name"] for m in scan["end_to_end"]] == [
        "records_per_s", "setup_s"]
    assert [m["name"] for m in scan["per_layer"]] == [
        "route_share.scan", "upsert_share.scan", "hashshard_roofline.scan",
        "ddsketch_roofline.scan", "device_idle.scan",
        "route_encode_share.scan", "route_device_share.scan",
        "upsert_split_share.scan", "upsert_assign_share.scan",
        "upsert_write_share.scan"]
    assert harness.driver("find").run
