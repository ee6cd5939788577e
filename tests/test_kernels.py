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
from repro.kernels.hashshard.ref import (encode_strings,
                                         encode_strings_joined,
                                         hashshard_host, hashshard_ref)
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


def _stacked_streams(rng, n, p, n_streams=5):
    """Five principal streams over one value column, as the snapshot
    aggregate step builds them: uid and gid slots, then directory levels
    whose negative slots are clamped to 0 with mask 0. Some rows are
    masked in every stream, some put the same principal in two streams,
    and some values fall in the zero bucket."""
    vals = rng.lognormal(8, 3, n).astype(np.float32)
    vals[rng.random(n) < 0.1] = 0.0
    pids = rng.integers(0, p, (n_streams, n)).astype(np.int32)
    mask = np.ones((n_streams, n), np.float32)
    raw = rng.integers(-1, p, (n_streams - 2, n))
    pids[2:] = np.maximum(raw, 0)
    mask[2:] = raw >= 0
    twice = rng.random(n) < 0.2
    pids[3, twice] = pids[2, twice]
    mask[3, twice] = mask[2, twice]
    mask[:, rng.random(n) < 0.1] = 0.0
    return vals, pids, mask


def _sequential_ref(cfg, vals, pids, mask, p):
    from repro.core.sketches import ddsketch as dds
    state = dds.init(cfg, (p,))
    for pid, m in zip(pids, mask):
        state = dds.update_grouped(cfg, state, jnp.asarray(vals),
                                   jnp.asarray(pid), p, jnp.asarray(m))
    return state


def _assert_stacked_equal(got, want):
    for k in ("counts", "zero_count", "count", "min", "max"):
        np.testing.assert_array_equal(np.asarray(got[k]), np.asarray(want[k]),
                                      err_msg=k)
    np.testing.assert_allclose(np.asarray(got["total"]),
                               np.asarray(want["total"]), rtol=1e-6)


@pytest.mark.parametrize("n,p,nb,rows,p_block", [
    (700, 45, 512, 128, 32),        # P and N off their block sizes
    (1200, 130, 256, 512, 128),     # P one past a block, N past two tiles
    (512, 16, 2048, 512, 128)])     # the real bucket count, one tile
def test_ddsketch_kernel_stacked_streams_match_sequential(n, p, nb, rows,
                                                          p_block):
    """S = 5 streams in one kernel call == five sequential grouped
    updates: bucket counts, zero count, count, min and max bit-for-bit
    (the one bf16 MXU pass is exact), total to float32 rounding."""
    cfg = DDSketchConfig(n_buckets=nb)
    vals, pids, mask = _stacked_streams(np.random.default_rng(n), n, p)
    got = grouped_update_pallas(cfg, jnp.asarray(vals), jnp.asarray(pids),
                                jnp.asarray(mask), p, rows=rows,
                                p_block=p_block)
    _assert_stacked_equal(got, _sequential_ref(cfg, vals, pids, mask, p))


def test_ddsketch_kernel_one_stream_is_exact():
    """(N,) ids and mask are S = 1: integer fields bit-identical to the
    reference, with bucket ids spread over all 2,048 buckets."""
    cfg = DDSketchConfig(n_buckets=2048)
    rng = np.random.default_rng(11)
    n, p = 1500, 200
    vals = np.exp(rng.uniform(-3, 40, n)).astype(np.float32)
    vals[:20] = 0.0
    pids = rng.integers(0, p, n).astype(np.int32)
    mask = (rng.random(n) > 0.1).astype(np.float32)
    got = grouped_update_pallas(cfg, jnp.asarray(vals), jnp.asarray(pids),
                                jnp.asarray(mask), p)
    _assert_stacked_equal(got, _sequential_ref(cfg, vals, pids[None],
                                               mask[None], p))


def test_ddsketch_ops_stacked_streams():
    """The entry point with (S, N) ids: the kernel path (interpret mode)
    and the CPU form (the reference once per stream) agree, onto a
    non-empty state, and mask=None means every stream is present."""
    from repro.core.sketches import ddsketch as dds
    from repro.kernels.ddsketch import ops as dd_ops
    cfg = DDSketchConfig(n_buckets=512)
    p = 40
    vals, pids, mask = _stacked_streams(np.random.default_rng(5), 600, p)
    vals, pids, mask = map(jnp.asarray, (vals, pids, mask))
    state = dds.update_grouped(cfg, dds.init(cfg, (p,)), vals[::-1],
                               pids[0], p)
    want = dd_ops.update_grouped(cfg, state, vals, pids, p, mask)
    _assert_stacked_equal(dd_ops.kernel_update_grouped(
        cfg, state, vals, pids, p, mask, interpret=True), want)
    _assert_stacked_equal(
        dd_ops.update_grouped(cfg, state, vals, pids, p),
        dd_ops.update_grouped(cfg, state, vals, pids, p,
                              jnp.ones(pids.shape, jnp.float32)))


def test_aggregate_step_kernel_path_matches_local(monkeypatch):
    """snapshot.make_aggregate_step through the kernel (interpret mode,
    one call per attribute over the five stacked streams) == the
    reference ``aggregate_local`` (twenty sequential jnp updates)."""
    import functools

    import jax

    from repro.core import snapshot as snap
    from repro.core.metadata import synth_filesystem
    from repro.kernels.ddsketch import ops as dd_ops
    from repro.launch.mesh import make_mesh
    calls = []
    kernel = functools.partial(dd_ops.kernel_update_grouped, interpret=True)

    def counted(cfg, state, values, pids, n, mask):
        calls.append(pids.shape)
        return kernel(cfg, state, values, pids, n, mask)
    monkeypatch.setattr(dd_ops, "on_tpu", lambda: True)
    monkeypatch.setattr(dd_ops, "kernel_update_grouped", counted)
    table = synth_filesystem(1500, n_users=16, n_groups=8, seed=2)
    pcfg = snap.PipelineConfig(n_users=16, n_groups=8, n_dirs=40,
                               sketch=snap.dds.DDSketchConfig(n_buckets=512))
    rows_np, valid_np = snap.pad_rows(snap.preprocess(table, pcfg), 512)
    rows = {k: jnp.asarray(v) for k, v in rows_np.items()}
    valid = jnp.asarray(valid_np)
    mesh = make_mesh((1, 1), ("data", "model"))
    got = jax.jit(snap.make_aggregate_step(pcfg, mesh))(rows, valid)
    levels = pcfg.dir_max - pcfg.dir_min + 1
    assert calls == [(2 + levels, len(valid_np))] * len(snap.ATTRS)
    _assert_stacked_equal(got, snap.aggregate_local(pcfg, rows, valid))


def test_hashshard_kernel_matches_host():
    strings = [f"/fs/project{i}/dir{i % 7}/file_{i}.dat" for i in range(300)]
    rows, lens = encode_strings(strings, width=64)
    h_dev, s_dev = hashshard_pallas(jnp.asarray(rows), jnp.asarray(lens))
    h_ref, s_ref = hashshard_ref(jnp.asarray(rows), jnp.asarray(lens))
    h_host, s_host = hashshard_host(strings)
    np.testing.assert_array_equal(np.asarray(h_dev), np.asarray(h_ref))
    np.testing.assert_array_equal(np.asarray(h_dev), h_host)
    np.testing.assert_array_equal(np.asarray(s_dev), s_host)


def test_hashshard_reads_only_bytes_within_length():
    """Joined-buffer rows hold the separator and the next paths past
    each row's length: the kernel and its jnp oracle still give the host
    hash, which reads exactly ``len`` bytes."""
    strings = [f"/fs/dé{i % 5}/f{i}" for i in range(300)] + [""]
    rows, lens, trunc = encode_strings_joined(strings, width=64)
    assert rows.shape[1] == 32 and not trunc.any()
    assert rows[0, lens[0]] == 0 and rows[0, lens[0] + 1] == ord("/")
    h_dev, s_dev = hashshard_pallas(jnp.asarray(rows), jnp.asarray(lens))
    h_ref, _ = hashshard_ref(jnp.asarray(rows), jnp.asarray(lens))
    h_host, s_host = hashshard_host(strings)
    np.testing.assert_array_equal(np.asarray(h_ref), h_host)
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
