"""Dry-run cells for the PAPER'S OWN pipelines on the production meshes.

Complements dryrun.py's 40 architecture cells with:

  icicle-counting   one counting-pipeline wave: 1M rows/device, 64Ki
                    principals sharded over "model", psum-merged counts
  icicle-aggregate  one aggregate-pipeline wave: grouped DDSketch update
                    (64Ki principals x 4 attrs x 2048 buckets) + psum merge
  icicle-monitor    one monitor tick per MDT: 8192-event reduction +
                    hierarchy pointer-jumping over 1M-fid state, one MDT
                    per device (the paper's monitor-per-MDT scaling rule)

Note: these lower the pure-jnp (scatter) formulation — the Pallas kernels
target real TPUs and are validated in interpret mode; XLA:CPU cannot
compile Mosaic kernels. Collective structure and memory are identical.
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import argparse  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402
from typing import Dict  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.core import events as ev  # noqa: E402
from repro.core import hierarchy as hi  # noqa: E402
from repro.core import reduction  # noqa: E402
from repro.core import snapshot as snap  # noqa: E402
from repro.core.sketches.ddsketch import DDSketchConfig  # noqa: E402
from repro.launch.dryrun import analyze_compiled  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402

ROWS_PER_DEVICE = 1 << 20      # counting
AGG_ROWS_PER_DEVICE = 1 << 19  # aggregate (sketch state is large)
N_PRINCIPALS = 1 << 16
EVENTS_PER_MDT = 8192
MAX_FIDS = 1 << 20


def _pipeline_cfg() -> snap.PipelineConfig:
    return snap.PipelineConfig(
        n_users=N_PRINCIPALS // 2, n_groups=N_PRINCIPALS // 4,
        n_dirs=N_PRINCIPALS // 4, sketch=DDSketchConfig(n_buckets=2048))


def _row_specs(n_rows: int) -> Dict:
    sd = jax.ShapeDtypeStruct
    return {
        "uid_slot": sd((n_rows,), jnp.int32),
        "gid_slot": sd((n_rows,), jnp.int32),
        "dir_slots": sd((n_rows, 3), jnp.int32),
        "shard_id": sd((n_rows,), jnp.int32),
        "size": sd((n_rows,), jnp.float32),
        "atime": sd((n_rows,), jnp.float32),
        "ctime": sd((n_rows,), jnp.float32),
        "mtime": sd((n_rows,), jnp.float32),
        "uid": sd((n_rows,), jnp.int32),
        "gid": sd((n_rows,), jnp.int32),
        "mode": sd((n_rows,), jnp.int32),
        "type": sd((n_rows,), jnp.int32),
        "path_hash": sd((n_rows,), jnp.uint32),
    }


def lower_counting(mesh):
    cfg = _pipeline_cfg()
    dp = ("pod", "data") if "pod" in mesh.axis_names else ("data",)
    n_dp = 1
    for a in dp:
        n_dp *= mesh.shape[a]
    n_rows = ROWS_PER_DEVICE * n_dp
    step = snap.make_counting_step(cfg, mesh, dp_axes=dp)
    rows = _row_specs(n_rows)
    valid = jax.ShapeDtypeStruct((n_rows,), jnp.bool_)
    in_sh = ({k: NamedSharding(mesh, P(dp, *([None] * (len(v.shape) - 1))))
              for k, v in rows.items()},
             NamedSharding(mesh, P(dp)))
    return jax.jit(step, in_shardings=in_sh).lower(rows, valid)


def lower_aggregate(mesh):
    cfg = _pipeline_cfg()
    dp = ("pod", "data") if "pod" in mesh.axis_names else ("data",)
    n_dp = 1
    for a in dp:
        n_dp *= mesh.shape[a]
    n_rows = AGG_ROWS_PER_DEVICE * n_dp
    step = snap.make_aggregate_step(cfg, mesh, dp_axes=dp)
    rows = _row_specs(n_rows)
    valid = jax.ShapeDtypeStruct((n_rows,), jnp.bool_)
    in_sh = ({k: NamedSharding(mesh, P(dp, *([None] * (len(v.shape) - 1))))
              for k, v in rows.items()},
             NamedSharding(mesh, P(dp)))
    return jax.jit(step, in_shardings=in_sh).lower(rows, valid)


def lower_monitor(mesh):
    """One monitor tick on every device: vmapped reduce+apply over the MDT
    axis, one MDT per chip (paper §IV-B4)."""
    all_axes = tuple(mesh.axis_names)
    n_mdt = mesh.devices.size

    def tick(state, batch, valid):
        def one(state, batch, valid):
            red = reduction.reduce_batch(batch, valid)
            return reduction.apply_batch(state, red, max_depth=64)
        return jax.vmap(one)(state, batch, valid)

    sd = jax.ShapeDtypeStruct
    state = {
        "parent": sd((n_mdt, MAX_FIDS), jnp.int32),
        "name_hash": sd((n_mdt, MAX_FIDS), jnp.uint32),
        "exists": sd((n_mdt, MAX_FIDS), jnp.bool_),
        "is_dir": sd((n_mdt, MAX_FIDS), jnp.bool_),
        "path_hash": sd((n_mdt, MAX_FIDS), jnp.uint32),
    }
    batch = {k: sd((n_mdt, EVENTS_PER_MDT), v.dtype)
             for k, v in ev.empty_batch(1).items()}
    valid = sd((n_mdt, EVENTS_PER_MDT), jnp.bool_)
    mdt_sharding = NamedSharding(mesh, P(all_axes))
    in_sh = (jax.tree.map(lambda _: mdt_sharding, state),
             jax.tree.map(lambda _: mdt_sharding, batch),
             mdt_sharding)
    return jax.jit(tick, in_shardings=in_sh, donate_argnums=(0,)
                   ).lower(state, batch, valid)


CELLS = {
    "icicle-counting": lower_counting,
    "icicle-aggregate": lower_aggregate,
    "icicle-monitor": lower_monitor,
}


def run_cell(name: str, multi_pod: bool) -> Dict:
    base = {"arch": name, "shape": "pipeline_wave",
            "mesh": "2x16x16" if multi_pod else "16x16", "tag": "icicle"}
    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        t0 = time.time()
        lowered = CELLS[name](mesh)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
        rec = analyze_compiled(lowered, compiled, None, None, mesh)
        rec.update(base)
        rec.update({"status": "ok", "lower_s": round(t_lower, 2),
                    "compile_s": round(t_compile, 2)})
        return rec
    except Exception as e:
        return {**base, "status": "error",
                "error": f"{type(e).__name__}: {e}",
                "traceback": traceback.format_exc()[-2000:]}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None)
    ap.add_argument("--cell", default=None)
    args = ap.parse_args()
    cells = [args.cell] if args.cell else list(CELLS)
    for name in cells:
        for mp in (False, True):
            rec = run_cell(name, mp)
            line = json.dumps({k: v for k, v in rec.items()
                               if k != "traceback"})
            print(line[:400])
            if args.out:
                with open(args.out, "a") as f:
                    f.write(json.dumps(rec) + "\n")
            jax.clear_caches()


if __name__ == "__main__":
    main()
