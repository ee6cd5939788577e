"""Pallas kernel validation (interpret=True) against pure-jnp oracles,
with hypothesis sweeps over shapes/distributions."""
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.sketches.ddsketch import DDSketchConfig
from repro.kernels.ddsketch.ddsketch import grouped_update_pallas
from repro.kernels.ddsketch.ref import grouped_update_ref
from repro.kernels.hashshard.hashshard import hashshard_pallas
from repro.kernels.hashshard.ref import (encode_strings, hashshard_host,
                                         hashshard_ref)
from repro.kernels.segstats.segstats import segstats_pallas
from repro.kernels.segstats.ref import segstats_ref


def _cmp_state(got, want, n_principals):
    np.testing.assert_allclose(np.asarray(got["counts"]),
                               np.asarray(want["counts"]), atol=1e-4)
    for k in ("zero_count", "count", "total"):
        np.testing.assert_allclose(np.asarray(got[k]), np.asarray(want[k]),
                                   rtol=1e-5, atol=1e-4)
    for k in ("min", "max"):
        g, w = np.asarray(got[k]), np.asarray(want[k])
        finite = np.isfinite(w)
        np.testing.assert_allclose(g[finite], w[finite], rtol=1e-6)
        assert not np.isfinite(g[~finite]).any()


@pytest.mark.parametrize("n,p,nb", [(100, 5, 256), (513, 17, 512),
                                    (2048, 128, 2048), (999, 130, 512)])
def test_ddsketch_kernel_matches_ref(n, p, nb):
    cfg = DDSketchConfig(n_buckets=nb)
    rng = np.random.default_rng(n)
    vals = jnp.asarray(rng.lognormal(8, 3, n), jnp.float32)
    pids = jnp.asarray(rng.integers(0, p, n), jnp.int32)
    mask = jnp.asarray(rng.random(n) > 0.1, jnp.float32)
    got = grouped_update_pallas(cfg, vals, pids, mask, p)
    want = grouped_update_ref(cfg, vals, pids, mask, p)
    _cmp_state(got, want, p)


@settings(max_examples=12, deadline=None)
@given(n=st.integers(8, 700), p=st.integers(1, 40),
       scale=st.sampled_from([1e-3, 1.0, 1e6, 1e12]), seed=st.integers(0, 99))
def test_ddsketch_kernel_property(n, p, scale, seed):
    cfg = DDSketchConfig(n_buckets=512)
    rng = np.random.default_rng(seed)
    vals = jnp.asarray(rng.exponential(scale, n), jnp.float32)
    pids = jnp.asarray(rng.integers(0, p, n), jnp.int32)
    mask = jnp.ones(n, jnp.float32)
    got = grouped_update_pallas(cfg, vals, pids, mask, p, rows=128,
                                p_block=32)
    want = grouped_update_ref(cfg, vals, pids, mask, p)
    _cmp_state(got, want, p)


def test_hashshard_kernel_matches_host():
    strings = [f"/fs/project{i}/dir{i % 7}/file_{i}.dat" for i in range(300)]
    rows, lens = encode_strings(strings, width=64)
    h_dev, s_dev = hashshard_pallas(jnp.asarray(rows), jnp.asarray(lens))
    h_ref, s_ref = hashshard_ref(jnp.asarray(rows), jnp.asarray(lens))
    h_host, s_host = hashshard_host(strings)
    np.testing.assert_array_equal(np.asarray(h_dev), np.asarray(h_ref))
    np.testing.assert_array_equal(np.asarray(h_dev), h_host)
    np.testing.assert_array_equal(np.asarray(s_dev), s_host)


@settings(max_examples=10, deadline=None)
@given(st.lists(st.text(min_size=0, max_size=60), min_size=1, max_size=80))
def test_hashshard_property(strings):
    rows, lens = encode_strings(strings, width=64)
    h_dev, s_dev = hashshard_pallas(jnp.asarray(rows), jnp.asarray(lens),
                                    rows=128)
    # device hash of the truncated utf-8 == host hash of the same bytes
    for i, s in enumerate(strings):
        raw = s.encode("utf-8")[:64]
        h = 0x811C9DC5
        for b in raw:
            h = ((h ^ b) * 0x01000193) & 0xFFFFFFFF
        assert int(h_dev[i]) == h


@pytest.mark.parametrize("n,p,s", [(257, 9, 64), (1024, 64, 16),
                                   (100, 200, 64)])
def test_segstats_kernel_matches_ref(n, p, s):
    rng = np.random.default_rng(7)
    pids = jnp.asarray(rng.integers(0, p, n), jnp.int32)
    sids = jnp.asarray(rng.integers(0, s, n), jnp.int32)
    vals = jnp.asarray(rng.lognormal(5, 2, n), jnp.float32)
    mask = jnp.asarray(rng.random(n) > 0.2, jnp.float32)
    got = segstats_pallas(pids, sids, vals, mask, p, s, rows=128, p_block=64)
    want = segstats_ref(pids, sids, vals, mask, p, s)
    np.testing.assert_allclose(np.asarray(got["counts"]),
                               np.asarray(want["counts"]))
    np.testing.assert_allclose(np.asarray(got["sum"]), np.asarray(want["sum"]),
                               rtol=1e-5)
    for k in ("min", "max"):
        g, w = np.asarray(got[k]), np.asarray(want[k])
        finite = np.isfinite(w)
        np.testing.assert_allclose(g[finite], w[finite], rtol=1e-6)


def test_kernel_ops_wrappers():
    """ops.py entry points: the kernel path (jit + state merge, interpret
    mode here) and the CPU form both match the jnp references, for the
    DDSketch update onto a non-empty state and for segstats."""
    from repro.core.sketches import ddsketch as dds
    from repro.kernels.ddsketch import ops as dd_ops
    from repro.kernels.segstats import ops as seg_ops
    cfg = DDSketchConfig(n_buckets=512)
    rng = np.random.default_rng(3)
    vals = jnp.asarray(rng.lognormal(8, 2, 500), jnp.float32)
    pids = jnp.asarray(rng.integers(0, 10, 500), jnp.int32)
    mask = jnp.ones_like(vals)
    state = dds.update_grouped(cfg, dds.init(cfg, (10,)), vals[::-1], pids,
                               10)
    want = dds.update_grouped(cfg, state, vals, pids, 10)
    _cmp_state(dd_ops.kernel_update_grouped(cfg, state, vals, pids, 10, mask,
                                            interpret=True), want, 10)
    _cmp_state(dd_ops.update_grouped(cfg, state, vals, pids, 10), want, 10)

    sids = jnp.asarray(rng.integers(0, 64, 500), jnp.int32)
    seg_want = segstats_ref(pids, sids, vals, mask, 10, 64)
    for got in (seg_ops.segstats_kernel(pids, sids, vals, mask, 10, 64,
                                        interpret=True),
                seg_ops.segstats(pids, sids, vals, mask, 10, 64)):
        for k in ("counts", "min", "max"):
            np.testing.assert_array_equal(np.asarray(got[k]),
                                          np.asarray(seg_want[k]))
        np.testing.assert_allclose(np.asarray(got["sum"]),
                                   np.asarray(seg_want["sum"]), rtol=1e-6)


@pytest.mark.parametrize("backend,compiled", [("tpu", True), ("cpu", False),
                                              ("gpu", None)])
def test_backend_choice(monkeypatch, backend, compiled):
    """The platform alone picks the kernel form: compiled Pallas on a
    TPU, the jnp oracle on the CPU, an error elsewhere."""
    import jax

    from repro.kernels import on_tpu
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if compiled is None:
        with pytest.raises(RuntimeError, match="gpu"):
            on_tpu()
    else:
        assert on_tpu() is compiled
