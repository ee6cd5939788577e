"""Compile the four Pallas kernels for a described TPU v5e chip at the
widths the main path runs them, and check the compile-cache helper.

Interpret mode accepts block shapes, casts and layouts the TPU compiler
refuses; these compiles catch that without a chip. The topology is
described inside a fixture (never at import time), so every test worker
collects the same tests and only the worker running this file loads the
TPU compiler library.

Each kernel's custom call carries its ``name=``, and the benchmark's
trace reduction (``bench/trace_reduce.kernel_of``, which goes by op
shape) still classifies that line as the same kernel.
"""
import os
import re
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.sketches.ddsketch import DDSketchConfig
from repro.core.snapshot import PipelineConfig
from repro.kernels.ddsketch.ddsketch import grouped_update_pallas
from repro.kernels.hashshard.hashshard import hashshard_pallas
from repro.kernels.predeval.predeval import predeval
from repro.kernels.predeval.ref import PRED_COLUMNS, SET_CAP
from repro.kernels.segstats.segstats import segstats_pallas
from repro.launch import compile_cache

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "bench"))
import trace_reduce  # noqa: E402

ARENA_ROWS = 1 << 20          # one shard's arena at 4M records / 4 shards
BATCH_ROWS = 1 << 16          # a routed / ingested device batch
ROUTE_WIDTH = 192             # ShardedPrimaryIndex.route_width


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    old_log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    was_on = jax.config.jax_enable_compilation_cache
    # compiles for a described chip are written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was_on)
        compilation_cache.reset_cache()
        if old_log_dir is None:
            os.environ.pop("TPU_LOG_DIR", None)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _custom_calls(compiled):
    """The Pallas custom-call lines of a compiled program's HLO, printed
    with operand shapes as the profiler's op names carry them."""
    from jax._src.lib import xla_client
    opts = xla_client._xla.HloPrintOptions.short_parsable()
    opts.print_operand_shape = True
    text = compiled.runtime_executable().hlo_modules()[0].to_string(opts)
    return [ln for ln in text.splitlines() if "tpu_custom_call" in ln
            and "custom-call(" in ln]


def _compile(fn, *shapes, kernel):
    """Compile ``fn`` for the chip; its one Pallas custom call is named
    ``kernel`` and classified as ``kernel`` (the ``name=`` given) by
    the trace reduction, from the HLO text with operand shapes the
    profiler's op names carry."""
    compiled = jax.jit(fn).lower(*shapes).compile()
    calls = _custom_calls(compiled)
    assert len(calls) == 1, calls
    name, kind = kernel
    assert re.match(rf"\s*(ROOT\s+)?%?{name}(\.\d+)?\s*=", calls[0]), \
        calls[0][:120]
    assert trace_reduce.kernel_of(calls[0]) == kind
    return compiled


@pytest.mark.parametrize("has_set", [False, True])
def test_predeval_compiles_for_v5e(one_chip, has_set):
    k, ks = 32, 4
    cols = len(PRED_COLUMNS)

    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def fn(*a):
        return predeval(*a, has_set=has_set)
    _compile(fn, s((3, ARENA_ROWS), jnp.float32),
             s((3, ARENA_ROWS), jnp.int32), s((ARENA_ROWS,), jnp.int32),
             s((k, cols), jnp.int32), s((k, cols), jnp.float32),
             s((k, cols), jnp.float32), s((k, cols), jnp.int32),
             s((ks,), jnp.int32), s((ks,), jnp.int32),
             s((ks, SET_CAP), jnp.int32), kernel=("predeval", "predeval"))


@pytest.mark.parametrize("rows", [1 << 21, 1 << 22])
def test_predeval_compiles_at_find_arena_shapes(one_chip, rows):
    """The IO500 find cell's arenas (8,388,608 records over 4 shards pad
    to 2^21 or 2^22 rows): one program (K = 1), no set."""
    from repro.kernels.predeval import ref as pk_ref
    progs = pk_ref.stack_programs([pk_ref.compile_program(
        [("size", "gt", 3900.5), ("size", "lt", 3901.5),
         ("mtime", "gt", 1.7e9)])])
    assert progs.k_pad == 1 and not progs.has_set

    def s(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    def fn(*a):
        return predeval(*a, has_set=False)
    _compile(fn, s(jax.ShapeDtypeStruct((3, rows), jnp.float32)),
             s(jax.ShapeDtypeStruct((3, rows), jnp.int32)),
             s(jax.ShapeDtypeStruct((rows,), jnp.int32)),
             *(s(a) for a in (progs.ops, progs.lo, progs.hi, progs.msk,
                              progs.setrows, progs.setcol, progs.setvals)),
             kernel=("predeval", "predeval"))


def test_segstats_compiles_for_v5e(one_chip):
    cfg = PipelineConfig()
    i32 = jax.ShapeDtypeStruct((BATCH_ROWS,), jnp.int32, sharding=one_chip)
    f32 = jax.ShapeDtypeStruct((BATCH_ROWS,), jnp.float32, sharding=one_chip)

    def fn(p, s, v, m):
        return segstats_pallas(p, s, v, m, cfg.n_principals, cfg.n_shards,
                               rows=512, p_block=128, interpret=False)
    _compile(fn, i32, i32, f32, f32, kernel=("segstats", "segstats"))


def test_ddsketch_compiles_for_v5e(one_chip):
    pcfg = PipelineConfig()
    cfg = DDSketchConfig(n_buckets=2048)
    streams = 2 + pcfg.dir_max - pcfg.dir_min + 1
    i32 = jax.ShapeDtypeStruct((streams, BATCH_ROWS), jnp.int32,
                               sharding=one_chip)
    f32 = jax.ShapeDtypeStruct((streams, BATCH_ROWS), jnp.float32,
                               sharding=one_chip)
    vals = jax.ShapeDtypeStruct((BATCH_ROWS,), jnp.float32, sharding=one_chip)

    def fn(v, p, m):
        return grouped_update_pallas(cfg, v, p, m, pcfg.n_principals,
                                     interpret=False)
    _compile(fn, vals, i32, f32,
             kernel=("ddsketch_grouped_update", "ddsketch"))


def test_aggregate_step_compiles_for_v5e(topo, monkeypatch):
    """The snapshot aggregate step at the rescan cell's shapes (one
    262,144-row chunk, the cell's pipeline configuration) makes one
    DDSketch kernel call per attribute, each named and classified as
    the benchmark's trace reduction expects."""
    import json

    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    import deploy
    from repro.core import snapshot as snap
    from repro.kernels.ddsketch import ops as dd_ops
    with open(os.path.join(os.path.dirname(trace_reduce.__file__), "configs",
                           "scan_refresh_4m.json")) as f:
        cell = json.load(f)
    pcfg = deploy.pipeline_config(cell["pipeline"])
    n = cell["index"]["chunk"]
    # a described chip: steer the entry point to its TPU form here
    monkeypatch.setattr(dd_ops, "on_tpu", lambda: True)
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"))
    levels = pcfg.dir_max - pcfg.dir_min + 1

    def s(shape, dt):
        spec = P("data", *([None] * (len(shape) - 1)))
        return jax.ShapeDtypeStruct(shape, dt,
                                    sharding=NamedSharding(mesh, spec))
    rows = {k: s((n,), jnp.int32) for k in
            ("uid_slot", "gid_slot", "shard_id", "uid", "gid", "mode",
             "type")}
    rows.update({k: s((n,), jnp.float32) for k in snap.ATTRS})
    rows["dir_slots"] = s((n, levels), jnp.int32)
    rows["path_hash"] = s((n,), jnp.uint32)
    compiled = jax.jit(snap.make_aggregate_step(pcfg, mesh)).lower(
        rows, s((n,), jnp.bool_)).compile()
    calls = _custom_calls(compiled)
    assert len(calls) == len(snap.ATTRS), [c[:120] for c in calls]
    for call in calls:
        assert re.match(r"\s*(ROOT\s+)?%?ddsketch_grouped_update(\.\d+)?\s*=",
                        call), call[:120]
        assert trace_reduce.kernel_of(call) == "ddsketch"


def test_hashshard_compiles_for_v5e(one_chip):
    rows = jax.ShapeDtypeStruct((BATCH_ROWS, ROUTE_WIDTH), jnp.uint8,
                                sharding=one_chip)
    lens = jax.ShapeDtypeStruct((BATCH_ROWS,), jnp.int32, sharding=one_chip)

    def fn(b, n):
        return hashshard_pallas(b, n, 4, interpret=False)
    _compile(fn, rows, lens, kernel=("hashshard", "hashshard"))


@pytest.fixture
def cache_dir_config():
    old = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", old)


def test_compile_cache_keeps_env_dir(monkeypatch, cache_dir_config, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.configure_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_inside_checkout(monkeypatch,
                                                cache_dir_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    got = compile_cache.configure_compile_cache()
    checkout = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert got == os.path.join(checkout, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == got
    # the same path on every call: a cache that moves never hits
    assert compile_cache.configure_compile_cache() == got
    with open(os.path.join(checkout, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()

