"""A whole run of each kind of cell at a tiny size on the CPU, with the
chip check skipped: a sound run is correct, and the run comes out not
correct when the timed path is broken underneath (a step that leaves its
state unchanged, half of a batch left out, an answer altered where it is
produced) or when the control stands in for the program."""
import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))

import harness  # noqa: E402
import run  # noqa: E402

TINY_CFG = {"namespace": {"n_files": 4096},
            "index": {"chunk": 4096, "sample_per_chunk": 64}}


def _go(cell, capsys, control=""):
    rc = run.main(["--workload", cell, "--seed", "4294967311",
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


def _scan_fault(kind, monkeypatch):
    from repro.core import snapshot as snap
    from repro.core.sharded_index import ShardedPrimaryIndex
    from repro.core.sketches import ddsketch as dds
    if kind == "state_unchanged":
        def make(pcfg, mesh, *a, **k):
            return lambda rows, valid: dds.init(
                pcfg.sketch, (pcfg.n_principals, len(snap.ATTRS)))
        monkeypatch.setattr(snap, "make_aggregate_step", make)
    elif kind == "half_batch":
        orig = ShardedPrimaryIndex.upsert_batch

        def upsert(self, paths, fields, versions, hashes=None):
            h = len(paths) // 2
            return orig(self, paths[:h], {k: v[:h] for k, v in
                                          fields.items()},
                        versions[:h], None if hashes is None else hashes[:h])
        monkeypatch.setattr(ShardedPrimaryIndex, "upsert_batch", upsert)
    elif kind == "answer_altered":
        orig = ShardedPrimaryIndex.route

        def route(self, paths, hashes=None):
            h, s = orig(self, paths, hashes)
            h = h.copy()
            h[0] ^= 1
            return h, s
        monkeypatch.setattr(ShardedPrimaryIndex, "route", route)


@pytest.mark.parametrize("cell", ["scan_refresh_4m.rescan"])
def test_sound_run_is_correct(cell, capsys):
    line = _go(cell, capsys)
    assert line["correct"], line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 0


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "answer_altered"])
def test_scan_fault_is_caught(fault, capsys, monkeypatch):
    _scan_fault(fault, monkeypatch)
    line = _go("scan_refresh_4m.rescan", capsys)
    assert not line["correct"], line["checks"]


@pytest.mark.parametrize("cell,control", [("scan_refresh_4m.rescan",
                                           "bf16")])
def test_control_is_not_correct(cell, control, capsys):
    line = _go(cell, capsys, control=control)
    assert not line["correct"], line["checks"]
