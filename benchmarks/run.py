"""Benchmark harness: one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only NAME]

Each bench prints its CSV block and paper-claim validation verdicts;
the harness exits non-zero if any validation fails.
"""
from __future__ import annotations

import argparse
import sys
import time


BENCHES = [
    ("table5_pipeline", "benchmarks.bench_pipeline"),
    ("table7_sketch_error", "benchmarks.bench_sketch_error"),
    ("table8_monitor", "benchmarks.bench_monitor"),
    ("event_ingest", "benchmarks.bench_event_ingest"),
    ("sharded_index", "benchmarks.bench_sharded"),
    ("reconcile", "benchmarks.bench_reconcile"),
    ("durable_pipeline", "benchmarks.bench_durable_pipeline"),
    ("discovery", "benchmarks.bench_discovery"),
    ("predeval", "benchmarks.bench_predeval"),
    ("query_service", "benchmarks.bench_query_service"),
    ("replication", "benchmarks.bench_replication"),
    ("rollup", "benchmarks.bench_rollup"),
    ("fig3_5_scaling", "benchmarks.bench_scaling"),
    ("table1_queries", "benchmarks.bench_index_query"),
    ("roofline", "benchmarks.bench_roofline"),
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    args = ap.parse_args()

    import importlib

    from repro.launch.compile_cache import configure_compile_cache
    configure_compile_cache()
    all_fails = []
    for name, mod_name in BENCHES:
        if args.only and args.only not in name:
            continue
        print(f"\n===== {name} ({mod_name}) =====")
        t0 = time.perf_counter()
        mod = importlib.import_module(mod_name)
        fails = mod.main() or []
        all_fails.extend((name, f) for f in fails)
        print(f"----- {name} done in {time.perf_counter() - t0:.1f}s -----")
    print("\n===== SUMMARY =====")
    if all_fails:
        for name, f in all_fails:
            print(f"FAIL [{name}] {f}")
        sys.exit(1)
    print("all paper-claim validations passed")


if __name__ == "__main__":
    main()
