"""Record a small profiler trace of the four kernels on the chip and
print what its planes, lines and events are called.

    python bench/record_trace.py --out <directory>

Each kernel runs a few times at a small size inside a host
``TraceAnnotation``; the ``.xplane.pb`` goes under ``--out``. The
benchmark's trace reduction (``bench/trace_reduce.py``) is keyed on the
names this prints, and its test reads a trace recorded this way.
"""
from __future__ import annotations

import argparse
import glob
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402


def run_kernels(reps: int) -> None:
    from repro.core import snapshot as snap
    from repro.core.query import pred_spec
    from repro.core.sketches import ddsketch as dds
    from repro.kernels.ddsketch import ops as dd_ops
    from repro.kernels.hashshard import ops as hs_ops
    from repro.kernels.predeval import ops as pk_ops
    from repro.kernels.predeval import ref as pk_ref
    from repro.kernels.segstats import ops as seg_ops

    rng = np.random.default_rng(0)
    n = 1 << 14
    rows = rng.integers(32, 127, (n, 192)).astype(np.uint8)
    lens = rng.integers(20, 60, n).astype(np.int32)
    cols = {"size": rng.lognormal(9, 2.5, n).astype(np.float32),
            "atime": (1.7e9 - rng.exponential(1e7, n)).astype(np.float32),
            "mtime": (1.7e9 - rng.exponential(1e7, n)).astype(np.float32),
            "uid": rng.integers(0, 256, n).astype(np.int32),
            "mode": np.full(n, 0o644, np.int32)}
    arena = pk_ops.pack_arena(cols, np.ones(n, bool), n)
    progs = pk_ref.stack_programs([pk_ref.compile_program(
        pred_spec("large_cold_files", (1e6, 9e6), {}, 1.7e9))] * 8)
    pcfg = snap.PipelineConfig()
    vals = jnp.asarray(cols["size"][:4096])
    pids = jnp.asarray(rng.integers(0, pcfg.n_principals, 4096), jnp.int32)
    mask = jnp.ones(4096, jnp.float32)
    state = dds.init(pcfg.sketch, (pcfg.n_principals,))
    for _ in range(reps):
        with jax.profiler.TraceAnnotation("bench.route"):
            h, _ = hs_ops.hashshard_route(rows, lens, 4)
            np.asarray(h)
        with jax.profiler.TraceAnnotation("bench.query"):
            pk_ops.predeval_words(arena, progs)
        with jax.profiler.TraceAnnotation("bench.aggregate"):
            st = dd_ops.update_grouped(pcfg.sketch, state, vals, pids,
                                       pcfg.n_principals, mask)
            jax.block_until_ready(st)
        with jax.profiler.TraceAnnotation("bench.count"):
            seg = seg_ops.segstats(pids, pids % 64, vals, mask,
                                   pcfg.n_principals, 64)
            jax.block_until_ready(seg)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", required=True)
    ap.add_argument("--reps", type=int, default=3)
    args = ap.parse_args(argv)
    if jax.devices()[0].platform != "tpu":
        sys.exit("record_trace: no TPU")
    run_kernels(1)                      # compile outside the trace
    jax.profiler.start_trace(args.out)
    run_kernels(args.reps)
    jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(args.out, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    print("trace:", path, os.path.getsize(path), "bytes")
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        lines = list(plane.lines)
        print(f"plane {plane.name!r}: {len(lines)} lines")
        for line in lines:
            evs = list(line.events)
            names = {}
            for e in evs:
                names.setdefault(e.name, [0, 0.0])
                names[e.name][0] += 1
                names[e.name][1] += e.duration_ns
            top = sorted(names.items(), key=lambda kv: -kv[1][1])[:12]
            print(f"  line {line.name!r}: {len(evs)} events")
            for nm, (c, d) in top:
                print(f"    {c:4d} x {d / 1e3:10.1f} us  {nm[:110]}")
            if evs:
                print("    stats of first:",
                      [(k, str(v)[:60]) for k, v in evs[0].stats][:8])


if __name__ == "__main__":
    main()
