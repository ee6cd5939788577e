"""The harness finds cells, traffic mixes and per-layer metrics by name,
and refuses to measure without a TPU."""
import json
import os
import shutil
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import harness  # noqa: E402


def test_every_cell_and_metric_resolves():
    bj = harness.benchmark()
    for w in bj["workloads"]:
        cell = harness.load_cell(w["name"])
        assert cell["cfg"]["name"] == w["config"]
        assert any(m["name"] == "setup_s" for m in cell["end_to_end"])
        assert len(cell["end_to_end"]) >= 2 and cell["per_layer"]
        for m in cell["per_layer"]:
            assert callable(harness.metric_reader(m["name"]))
            assert m["moves"] in {e["name"] for e in cell["end_to_end"]}
        harness.driver(cell["cfg"]["kind"])


def test_a_cell_added_as_files_is_found(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "data"))
    bj = harness.benchmark()
    (tmp_path / "bench" / "traffic" / "churn_half.json").write_text(
        json.dumps({"change_frac": 0.5}))
    (tmp_path / "bench" / "metrics" / "chunks_in_window.half.py"
     ).write_text("def read(ctx):\n    return 42.0\n")
    bj["workloads"].append({
        "name": "scan_refresh_4m.churn_half", "config": "scan_refresh_4m",
        "traffic": "churn_half", "chips": 1, "why": "test"})
    rate = next(m for m in bj["end_to_end"]
                if m["name"] == "records_per_s")
    rate["workloads"].append("scan_refresh_4m.churn_half")
    bj["per_layer"].append({
        "name": "chunks_in_window.half", "unit": "chunks",
        "better": "higher", "source": "program_span",
        "layer": "index upsert", "moves": "records_per_s",
        "workloads": ["scan_refresh_4m.churn_half"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bj))
    cell = harness.load_cell("scan_refresh_4m.churn_half",
                             root=str(tmp_path))
    assert cell["mix"]["change_frac"] == 0.5
    assert [m["name"] for m in cell["per_layer"]] == [
        "chunks_in_window.half"]
    assert harness.metric_reader("chunks_in_window.half",
                                 root=str(tmp_path))({}) == 42.0


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("ALLOW_MULTIPLE_LIBTPU_LOAD", None)
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "scan_refresh_4m.rescan", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=240)


def test_no_tpu_exits_nonzero_with_a_message():
    p = _run(ROOT)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert p.stdout.strip() == ""


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    p = _run(str(tmp_path))
    assert p.returncode != 0
    assert p.stdout.strip() == ""


@pytest.mark.parametrize("key", ["command", "paths", "run_seconds",
                                 "configs", "workloads", "end_to_end",
                                 "per_layer"])
def test_benchmark_json_has_the_contract_keys(key):
    bj = harness.benchmark()
    assert set(bj) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    assert bj[key]
