"""The benchmark's copied generators: deterministic per seed, and drawing
from the same distributions as the program's originals."""
import os
import sys
import zlib

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))

import corpus  # noqa: E402
import reference  # noqa: E402

PCFG = {"n_users": 256, "n_groups": 64, "n_dirs": 1024, "dir_min": 1,
        "dir_max": 3, "n_shards": 64}


@pytest.mark.parametrize("seed", [0, 7, 2 ** 33 + 5])
def test_namespace_equals_the_program_generator(seed):
    from repro.core.metadata import synth_filesystem
    want = synth_filesystem(3000, n_users=256, n_groups=64, n_dirs=75,
                            seed=seed)
    got = corpus.synth_namespace(3000, n_users=256, n_groups=64,
                                 n_dirs=75, seed=seed)
    assert list(got.paths) == list(want.paths)
    for k in ("parent", "depth", "type", "mode", "uid", "gid", "size",
              "atime", "ctime", "mtime", "fileset"):
        assert np.array_equal(getattr(got, k), getattr(want, k)), k


def test_pipeline_rows_equal_the_program_preprocessing():
    from repro.core import snapshot as snap
    from repro.core.metadata import synth_filesystem
    table = synth_filesystem(4000, n_users=256, n_groups=64, n_dirs=100,
                             seed=3)
    want = snap.preprocess(table, snap.PipelineConfig())
    got = corpus.pipeline_rows(corpus.synth_namespace(
        4000, n_users=256, n_groups=64, n_dirs=100, seed=3), PCFG)
    for k in ("uid_slot", "gid_slot", "dir_slots", "shard_id", "size",
              "atime", "ctime", "mtime", "uid", "gid", "mode", "type"):
        assert np.array_equal(got[k], want[k]), k


def test_fnv_reference_matches_the_program_hash():
    from repro.core.metadata import path_hash
    paths = ["/fs", "/fs/d1/f2", "/fs/" + "x" * 300, "/fs/café"]
    assert reference.fnv1a(paths).tolist() == [path_hash(p) for p in paths]
    assert zlib.crc32(b"/fs") >= 0


def test_shape_seed_gives_every_seed_the_same_work():
    spec = {"n_files": 3000, "n_users": 256, "n_groups": 64,
            "files_per_dir": 40, "now": 1.7e9, "shape_seed": 0}
    a = corpus.namespace_for(spec, 1)
    b = corpus.namespace_for(spec, 2 ** 33 + 5)
    assert list(corpus.namespace_for(spec, 1).paths) == list(a.paths)
    assert list(a.paths[:a.n_dirs]) == list(b.paths[:b.n_dirs])
    fa, fb = a.files(), b.files()
    assert list(fa.paths) != list(fb.paths)
    assert sorted(fa.paths) == sorted(fb.paths)
    # each file keeps its directory, owner, size and times
    row = {p: i for i, p in enumerate(fb.paths)}
    j = np.array([row[p] for p in fa.paths])
    for k in ("parent", "depth", "uid", "gid", "size", "mtime", "mode"):
        assert np.array_equal(getattr(fa, k), getattr(fb, k)[j]), k


@pytest.mark.parametrize("seed", [1, 2 ** 33 + 5])
def test_every_seed_changes_the_same_count(seed):
    import scan
    ns = corpus.synth_namespace(4000, n_users=256, n_groups=64, n_dirs=100,
                                seed=0)
    files = ns.files()
    rows = corpus.pipeline_rows(ns, PCFG)
    views = scan.variants(files, rows, 0.05, seed, 1.7e9)
    assert [v["changed"] for v in views] == [200, 200]
    for v in views:
        on = v["size"] != rows["size"]
        assert on.sum() <= 200 and (v["mtime"] != rows["mtime"]).sum() == 200
