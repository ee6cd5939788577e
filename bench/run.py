"""The benchmark's command: one run of one cell.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

It finds the cell in ``BENCHMARK.json``, checks for the chips the cell
needs (no TPU: exit 2, no result), keeps JAX's compile cache in the
checkout, builds the deployment from ``--seed`` and warms every shape
(``setup_s`` is the time from process start to the first timed
operation), measures for ``--seconds``, compares what the timed path
produced with the plain reference, and prints one JSON line last on
standard output. ``--trace 0`` reports the cell's end-to-end metrics;
``--trace 1`` traces the device over the same window and reports the
per-layer metrics, ``busy_s``/``window_s`` and a breakdown. The numbers
compared, each beside its limit, end standard error and the line's
``checks``.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))

import harness  # noqa: E402


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def merge(base, over):
    """``over`` laid onto ``base``, nested dicts merged key by key."""
    out = dict(base)
    for k, v in over.items():
        out[k] = merge(base[k], v) if isinstance(v, dict) \
            and isinstance(base.get(k), dict) else v
    return out


def per_layer(cell, ctx, device_kind):
    out = {}
    for m in cell["per_layer"]:
        v = harness.metric_reader(m["name"])(dict(ctx,
                                                   device_kind=device_kind))
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def main(argv=None, require_chip: bool = True, overrides=None,
         control: str = "", trace_dir: str = "") -> int:
    args = parse_args(argv)
    cell = harness.load_cell(args.workload)
    if overrides:
        cell["cfg"] = merge(cell["cfg"], overrides.get("cfg", {}))
        cell["mix"] = merge(cell["mix"], overrides.get("mix", {}))
    import jax
    try:
        device = harness.require_chips(int(cell["chips"]))
    except harness.NoChip as e:
        if require_chip:
            print(f"bench/run.py: {e}", file=sys.stderr)
            return 2
        dev = jax.devices()[0]
        device = {"platform": dev.platform, "kind": dev.device_kind,
                  "count": int(cell["chips"])}
    harness.configure_compile_cache()
    spans = harness.Spans()
    compiles = harness.CompileCounter()
    tdir = trace_dir or (tempfile.mkdtemp(prefix="bench_trace_")
                         if args.trace else "")
    tracer = harness.Tracer(bool(args.trace), tdir)
    drv = harness.driver(cell["cfg"]["kind"])
    res = drv.run(cell, args.seed, args.seconds, bool(args.trace), spans,
                  tracer, compiles, harness.log, control=control)
    device = dict(device, memory_peak_bytes=int(res["memory"]))
    breakdown = None
    units = {m["name"]: m["unit"] for m in cell["end_to_end"]}
    if args.trace:
        import trace_reduce
        red = trace_reduce.reduce_dir(tdir, n_chips=int(cell["chips"]))
        if not trace_dir:
            shutil.rmtree(tdir, ignore_errors=True)
        ctx = dict(res["ctx"], trace=red)
        metrics = per_layer(cell, ctx, device["kind"])
        if red is not None:
            device["busy_s"] = red["busy_s"]
            device["window_s"] = red["window_s"]
            breakdown = {"device_ops": red["device_ops"],
                         "idle_gaps": red["idle_gaps"]}
            harness.log(f"trace: kernels {red['kernel_s']}, calls "
                        f"{red['kernel_calls']}")
    else:
        metrics = {k: {"value": float(v), "unit": units[k]}
                   for k, v in res["e2e"].items() if k in units}
        metrics["setup_s"] = {"value": res["t_first"] - T_PROCESS,
                              "unit": units["setup_s"]}
    checks = [{"name": k, "value": v, "limit": lim}
              for k, (v, lim) in res["checks"].items()]
    correct = res["failed"] == 0 and all(c["value"] <= c["limit"]
                                         for c in checks)
    for c in checks:
        harness.log(f"check {c['name']}: {c['value']} (limit {c['limit']})")
    print(harness.result_line(correct, res["attempted"], res["failed"],
                              metrics, device, checks, breakdown),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
