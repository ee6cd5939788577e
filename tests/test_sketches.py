"""Sketch correctness: error guarantees, mergeability, grouped updates,
and paper Table VII's accuracy figures."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.metadata import files_only, synth_filesystem
from repro.core.sketches import DDSketch, KLLSketch, ReqSketch, TDigest
from repro.core.sketches import ddsketch as dds

QS = (0.10, 0.25, 0.50, 0.75, 0.90, 0.99)


def _lognormal(n, seed=0):
    return np.random.default_rng(seed).lognormal(9.0, 2.5, n)


def test_ddsketch_jnp_relative_error():
    cfg = dds.DEFAULT
    vals = _lognormal(20000)
    state = dds.init(cfg)
    state = dds.update(cfg, state, jnp.asarray(vals, jnp.float32))
    for q in QS:
        est = float(dds.quantile(cfg, state, q))
        exact = float(np.quantile(vals, q, method="lower"))
        assert abs(est - exact) / exact < 2.5 * cfg.alpha, (q, est, exact)


def test_ddsketch_merge_equals_bulk():
    cfg = dds.DEFAULT
    vals = _lognormal(8000)
    s1 = dds.update(cfg, dds.init(cfg), jnp.asarray(vals[:3000], jnp.float32))
    s2 = dds.update(cfg, dds.init(cfg), jnp.asarray(vals[3000:], jnp.float32))
    merged = dds.merge(s1, s2)
    bulk = dds.update(cfg, dds.init(cfg), jnp.asarray(vals, jnp.float32))
    for q in QS:
        np.testing.assert_allclose(float(dds.quantile(cfg, merged, q)),
                                   float(dds.quantile(cfg, bulk, q)),
                                   rtol=1e-6)


def test_ddsketch_grouped_matches_per_group():
    cfg = dds.DDSketchConfig(n_buckets=512)
    rng = np.random.default_rng(1)
    vals = rng.lognormal(6, 2, 5000)
    pids = rng.integers(0, 7, 5000)
    gstate = dds.init(cfg, (7,))
    gstate = dds.update_grouped(cfg, gstate, jnp.asarray(vals, jnp.float32),
                                jnp.asarray(pids, jnp.int32), 7)
    for p in range(7):
        ref = dds.update(cfg, dds.init(cfg),
                         jnp.asarray(vals[pids == p], jnp.float32))
        sub = jax.tree.map(lambda s: s[p], gstate)
        np.testing.assert_allclose(np.asarray(sub["counts"]),
                                   np.asarray(ref["counts"]))
        for q in (0.25, 0.5, 0.99):
            np.testing.assert_allclose(float(dds.quantile(cfg, sub, q)),
                                       float(dds.quantile(cfg, ref, q)),
                                       rtol=1e-6)


def test_ddsketch_host_matches_jnp():
    vals = _lognormal(10000, seed=3)
    host = DDSketch()
    host.update(vals)
    cfg = host.cfg
    state = dds.update(cfg, dds.init(cfg), jnp.asarray(vals, jnp.float32))
    for q in QS:
        hq = host.quantile(q)
        jq = float(dds.quantile(cfg, state, q))
        assert abs(hq - jq) / max(hq, 1e-9) < 0.02, (q, hq, jq)


#: paper Table VII's three synthetic snapshots (FS-small/medium/large
#: analogues), cut to 6k/15k/30k files; principal counts kept
TABLE_VII_FS = {
    "FS-small": dict(n_files=6_000, n_users=12, n_groups=4, seed=1),
    "FS-medium": dict(n_files=15_000, n_users=40, n_groups=12, seed=2),
    "FS-large": dict(n_files=30_000, n_users=120, n_groups=24, seed=3),
}


@functools.lru_cache(maxsize=None)
def _principal_values(fs):
    """Each attribute's values per user/group with >= 100 files."""
    f = files_only(synth_filesystem(**TABLE_VII_FS[fs]))
    out = []
    for col in (f.uid, f.gid):
        for p in np.unique(col):
            mask = col == p
            if mask.sum() >= 100:
                out += [vals[mask] for vals in (f.size, f.atime, f.ctime,
                                                f.mtime)]
    return out


def _table_vii_errors(cls, fs, n_shards=8):
    """Table VII's metrics over one snapshot: mean normalized rank error
    and mean relative value error over p10..p99 of every principal's
    attributes, each sketch built per shard and merged (the pipeline's
    structure)."""
    rank_errs, val_errs = [], []
    for vals in _principal_values(fs):
        shards = np.array_split(vals, n_shards)
        sk = cls()
        sk.update(shards[0])
        for sh in shards[1:]:
            other = cls()
            other.update(sh)
            sk.merge(other)
        sv = np.sort(vals)
        n = len(vals)
        for q in QS:
            est = sk.quantile(q)
            exact = float(np.quantile(vals, q, method="lower"))
            rank_errs.append(abs(np.searchsorted(sv, est) - q * n) / n)
            if abs(exact) > 1e-12:
                val_errs.append(abs(est - exact) / abs(exact))
    return float(np.mean(rank_errs)), float(np.mean(val_errs))


@pytest.mark.parametrize("cls,data", [
    *(pytest.param(cls, "lognormal", id=cls.__name__)
      for cls in (KLLSketch, ReqSketch, TDigest)),
    *(pytest.param(cls, fs, id=f"{cls.__name__}-{fs}")
      for cls in (KLLSketch, ReqSketch, TDigest) for fs in TABLE_VII_FS)])
def test_host_sketch_rank_error(cls, data):
    """Paper Table VII: KLL, Req and t-Digest keep the mean normalized
    rank error under 0.11, on one lognormal stream (every quantile) and
    on each snapshot's principals (the mean)."""
    if data != "lognormal":
        rank_err, _ = _table_vii_errors(cls, data)
        assert rank_err < 0.11, (cls.name, data, rank_err)
        return
    vals = _lognormal(20000, seed=5)
    sk = cls()
    sk.update(vals)
    sv = np.sort(vals)
    n = len(vals)
    for q in QS:
        est = sk.quantile(q)
        rank = np.searchsorted(sv, est)
        assert abs(rank - q * n) / n < 0.12, (cls.name, q, rank / n)


@pytest.mark.parametrize("fs", list(TABLE_VII_FS))
def test_ddsketch_table_vii_value_error(fs):
    """Paper Table VII: DDSketch's mean relative value error stays under
    0.01 on every snapshot."""
    _, val_err = _table_vii_errors(DDSketch, fs)
    assert val_err < 0.01, (fs, val_err)


@pytest.mark.parametrize("cls", [KLLSketch, ReqSketch, TDigest, DDSketch])
def test_host_sketch_merge(cls):
    vals = _lognormal(12000, seed=7)
    a, b = cls(), cls()
    a.update(vals[:5000])
    b.update(vals[5000:])
    a.merge(b)
    full = cls()
    full.update(vals)
    sv = np.sort(vals)
    n = len(vals)
    for q in (0.25, 0.5, 0.9):
        est = a.quantile(q)
        rank = np.searchsorted(sv, est)
        assert abs(rank - q * n) / n < 0.15, (cls.name, q)


@settings(max_examples=25, deadline=None)
@given(st.lists(st.floats(min_value=1e-3, max_value=1e12,
                          allow_nan=False, allow_infinity=False),
                min_size=10, max_size=400),
       st.sampled_from([0.1, 0.5, 0.9, 0.99]))
def test_ddsketch_property_relative_error(values, q):
    """Property: DDSketch quantile is within alpha relative error of an
    exact quantile for arbitrary positive inputs."""
    cfg = dds.DEFAULT
    vals = np.asarray(values)
    state = dds.update(cfg, dds.init(cfg), jnp.asarray(vals, jnp.float32))
    est = float(dds.quantile(cfg, state, q))
    exact = float(np.quantile(vals, q, method="lower"))
    if exact > cfg.min_value:
        assert abs(est - exact) / exact < 3 * cfg.alpha + 1e-4
