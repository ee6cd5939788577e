"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

MUST be imported/run fresh: the XLA_FLAGS below must be set before jax
initializes devices (jax locks the device count on first init).

Usage:
  python -m repro.launch.dryrun --arch olmo-1b --shape train_4k [--multi-pod]
  python -m repro.launch.dryrun --sweep --out results/dryrun.jsonl
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import argparse  # noqa: E402
import json  # noqa: E402
import re  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402
from collections import defaultdict  # noqa: E402
from typing import Dict, Optional, Tuple  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro import models  # noqa: E402
from repro.configs import SHAPES, get_config, shape_applicable  # noqa: E402
from repro.configs.all_archs import ARCH_IDS  # noqa: E402
from repro.data import specs as dspecs  # noqa: E402
from repro.distributed import sharding as shd  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.serving.engine import make_prefill_step, make_serve_step  # noqa: E402
from repro.training.optimizer import AdamWConfig, init_opt_state  # noqa: E402
from repro.training.steps import (batch_shardings, make_train_shardings,  # noqa: E402
                                  make_train_step)

# ---------------------------------------------------------------------------
# cell lowering
# ---------------------------------------------------------------------------

def lower_cell(arch: str, shape_name: str, mesh, step_override=None):
    """Returns jax Lowered for the cell's step function."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    if shape.kind in ("prefill", "decode"):
        # Serving deploys bf16 weights (f32 masters are a training-only
        # artifact) and NEVER fsdp-sharded params: per-layer all-gathers
        # per decoded token would dominate the step (§Perf iterations 1+6).
        cfg = cfg.replace(param_dtype="bfloat16", fsdp=False)
    if step_override is not None:
        cfg = step_override(cfg)
    desc = models.param_desc(cfg)
    aparams = models.abstract_params(cfg)

    if shape.kind == "train":
        psh, osh, bsh = make_train_shardings(cfg, mesh)
        mdt = "bfloat16" if cfg.param_dtype == "bfloat16" else "float32"
        aopt = jax.eval_shape(lambda p: init_opt_state(p, mdt), aparams)
        step = make_train_step(cfg, AdamWConfig(moment_dtype=mdt), mesh)
        jitted = jax.jit(step, in_shardings=(psh, osh, bsh),
                         out_shardings=(psh, osh, None),
                         donate_argnums=(0, 1))
        binput = dspecs.train_input_specs(cfg, shape)
        return jitted.lower(aparams, aopt, binput), cfg

    psh = shd.param_shardings(desc, cfg, mesh)
    if shape.kind == "prefill":
        step = make_prefill_step(cfg, mesh)
        bsh = batch_shardings(cfg, mesh)
        bsh.pop("labels", None)
        binput = dspecs.train_input_specs(cfg, shape)
        binput.pop("labels", None)
        jitted = jax.jit(step, in_shardings=(psh, bsh))
        return jitted.lower(aparams, binput), cfg

    # decode
    step = make_serve_step(cfg, mesh)
    batch, cache = dspecs.decode_input_specs(cfg, shape)
    csh = shd.cache_specs(cfg, cache, mesh)
    dp = shd.dp_axes(mesh)
    bsh = {}
    for k in batch:
        if k == "positions" and cfg.mrope_input:
            bsh[k] = NamedSharding(mesh, P(None, dp, None))
        elif k == "embeds":
            bsh[k] = NamedSharding(mesh, P(dp, None, None))
        else:
            bsh[k] = NamedSharding(mesh, P(dp, None))
    n_dp = 1
    for a in dp:
        n_dp *= mesh.shape[a]
    if shape.global_batch % n_dp != 0:  # e.g. long_500k batch=1
        bsh = {k: NamedSharding(mesh, P()) for k in batch}
    jitted = jax.jit(step, in_shardings=(psh, csh, bsh),
                     out_shardings=None, donate_argnums=(1,))
    return jitted.lower(aparams, cache, batch), cfg


def analyze_compiled(lowered, compiled, cfg, shape, mesh) -> Dict:
    from repro.analysis.hlocost import analyze_hlo

    ca = compiled.cost_analysis() or {}
    try:
        ma = compiled.memory_analysis()
        mem = {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
        }
    except Exception as e:  # pragma: no cover
        mem = {"error": str(e)}
    hlo = compiled.as_text()
    # trip-count-aware analysis; bf16-collective correction only applies to
    # bf16-compute model programs (icicle pipelines use genuine f32 sums)
    bf16 = bool(cfg is not None and cfg.dtype == "bfloat16")
    cost = analyze_hlo(hlo, bf16_collectives=bf16)
    n_chips = mesh.devices.size
    record = {
        # per-device numbers; xla_* are the raw (scan-body-once) versions
        "mxu_flops_per_device": cost.mxu_flops,
        "vpu_flops_per_device": cost.vpu_flops,
        "xla_flops_per_device": float(ca.get("flops", 0.0)),
        "xla_bytes_per_device": float(ca.get("bytes accessed", 0.0)),
        "memory": mem,
        "collectives": cost.coll,
        "coll_operand_bytes": cost.coll_operand_bytes,
        "coll_wire_bytes": cost.coll_wire_bytes,
        "n_chips": int(n_chips),
        "hlo_bytes": len(hlo),
    }
    return record


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             step_override=None, tag: str = "") -> Dict:
    shape = SHAPES[shape_name]
    cfg = get_config(arch)
    ok, reason = shape_applicable(cfg, shape)
    base = {"arch": arch, "shape": shape_name,
            "mesh": "2x16x16" if multi_pod else "16x16", "tag": tag}
    if not ok:
        return {**base, "status": "skipped", "reason": reason}
    t0 = time.time()
    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        lowered, cfg2 = lower_cell(arch, shape_name, mesh, step_override)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
        rec = analyze_compiled(lowered, compiled, cfg2, shape, mesh)
        rec.update(base)
        rec.update({
            "status": "ok",
            "lower_s": round(t_lower, 2),
            "compile_s": round(t_compile, 2),
            "param_count": cfg2.param_count(),
            "active_param_count": cfg2.active_param_count(),
        })
        return rec
    except Exception as e:
        return {**base, "status": "error", "error": f"{type(e).__name__}: {e}",
                "traceback": traceback.format_exc()[-2000:]}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--sweep", action="store_true")
    ap.add_argument("--out", default=None)
    ap.add_argument("--skip-done", action="store_true",
                    help="skip cells already present in --out")
    args = ap.parse_args()

    done = set()
    if args.out and args.skip_done and os.path.exists(args.out):
        with open(args.out) as f:
            for line in f:
                try:
                    r = json.loads(line)
                    if r.get("status") in ("ok", "skipped"):
                        done.add((r["arch"], r["shape"], r["mesh"]))
                except Exception:
                    pass

    def emit(rec):
        line = json.dumps(rec)
        if args.out:
            with open(args.out, "a") as f:
                f.write(line + "\n")
        slim = {k: v for k, v in rec.items() if k not in ("traceback",)}
        print(json.dumps(slim)[:400])

    if args.sweep:
        cells = []
        for arch in ARCH_IDS:
            for shape in SHAPES:
                for mp in (False, True):
                    cells.append((arch, shape, mp))
        for arch, shape, mp in cells:
            key = (arch, shape, "2x16x16" if mp else "16x16")
            if key in done:
                print("skip done:", key)
                continue
            emit(run_cell(arch, shape, mp))
            jax.clear_caches()  # bound compile-cache memory across 80 cells
        return

    rec = run_cell(args.arch, args.shape, args.multi_pod)
    emit(rec)
    if rec["status"] == "ok":
        print(f"memory_analysis: {rec['memory']}")
        print(f"cost: mxu/dev={rec['mxu_flops_per_device']:.3e} "
              f"vpu/dev={rec['vpu_flops_per_device']:.3e} "
              f"coll_wire={rec['coll_wire_bytes']:.3e}")
        print(f"collectives: {json.dumps(rec['collectives'])[:500]}")


if __name__ == "__main__":
    main()
