"""Quickstart: snapshot -> pipelines -> dual index -> queries -> live events.

    PYTHONPATH=src python examples/quickstart.py

Walks the full Icicle loop from the paper on a synthetic 20k-file system:
1. snapshot ingest (primary + counting + aggregate pipelines),
2. Table-I queries against both indexes,
3. real-time monitoring: apply a burst of changelog events and watch the
   monitor reduce/cancel them,
4. event-based index synchronization: the same monitor feeds the dual
   index through an EventIngestor, and queries report their freshness
   watermark (DESIGN.md §6).
"""
import sys

sys.path.insert(0, "src")

import jax.numpy as jnp
import numpy as np

from repro.core import events as ev
from repro.core import snapshot as snap
from repro.core.dashboard import render_dashboard, scheduled_report
from repro.core.event_ingest import EventIngestor, IngestConfig
from repro.core.index import AggregateIndex, PrimaryIndex
from repro.core.metadata import synth_filesystem
from repro.core.monitor import Monitor, MonitorConfig
from repro.core.query import QueryEngine
from repro.core.sketches.ddsketch import DDSketchConfig
from repro.launch.compile_cache import configure_compile_cache


def main():
    configure_compile_cache()
    print("== 1. snapshot ==")
    table = synth_filesystem(20_000, n_users=32, n_groups=8, seed=42)
    print(f"synthetic FS: {len(table)} objects")

    primary = PrimaryIndex()
    n = primary.ingest_table(table, version=1)
    print(f"primary index: {n} new records, {len(primary)} live")

    pcfg = snap.PipelineConfig(n_users=32, n_groups=8, n_dirs=88,
                               sketch=DDSketchConfig(alpha=0.02,
                                                     n_buckets=1024,
                                                     offset=64))
    rows_np, valid = snap.pad_rows(snap.preprocess(table, pcfg), 1024)
    rows = {k: jnp.asarray(v) for k, v in rows_np.items()}
    counts = snap.counting_local(pcfg, rows, jnp.asarray(valid))
    state = snap.aggregate_local(pcfg, rows, jnp.asarray(valid))
    agg = AggregateIndex()
    names = ([f"user:{i}" for i in range(32)]
             + [f"group:{i}" for i in range(8)]
             + [f"dir:{i}" for i in range(88)])
    agg.from_sketch_state(pcfg.sketch, state, names)
    print(f"aggregate index: {len(agg)} principals; counting pipeline "
          f"total={float(np.asarray(counts).sum()):.0f} object-slots")

    print("\n== 2. queries (Table I) ==")
    # now pinned to the synthetic corpus epoch for stable demo output
    q = QueryEngine(primary, agg, now=1.7e9)
    print("top storage users:", q.top_storage_users(3))
    print("world-writable files:", len(q.world_writable()))
    print("cold large files:", len(q.large_cold_files(1e9, 90 * 86400)))
    u0 = agg.get("user:1")
    if u0:
        print(f"user:1 summary: {u0['file_count']:.0f} files, "
              f"p99 size {u0['size']['p99']:.3g} B, "
              f"total {u0['size']['total']:.3g} B")

    print("\n== 3. live monitoring ==")
    stream = ev.EventStream(start_fid=1)
    ev.eval_perf_workload(stream, 500)          # create-modify-delete churn
    ev.mixed_workload(stream, 400, seed=1)
    mon = Monitor(MonitorConfig(max_fids=1 << 14, batch_size=1024))
    r = mon.run(stream)
    print(f"monitor: {r['events']} events at {r['events_per_s']:.0f}/s; "
          f"updates={mon.metrics['updates']} deletes={mon.metrics['deletes']} "
          f"cancelled={mon.metrics['cancelled']} "
          f"(reduction killed {mon.metrics['cancelled'] * 2} events)")

    print("\n== 4. event-based index sync + freshness ==")
    ing = EventIngestor(IngestConfig(mode="eager"), pcfg, primary, agg,
                        names={0: "fs"})
    q_live = QueryEngine(primary, agg, now=1.7e9, ingestor=ing)
    stream2 = ev.EventStream(start_fid=1 << 16)
    ev.filebench_workload(stream2, 300, 100, seed=2, has_stat=1,
                          n_users=32, n_groups=8)
    mon2 = Monitor(MonitorConfig(max_fids=1 << 17, batch_size=1024),
                   ingestor=ing)
    r2 = mon2.run(stream2)
    out = q_live.query("find_by_name", r"/f\d+$")
    fr = out["freshness"]
    print(f"monitor+ingest: {r2['events']} events, watermark seq "
          f"{fr['applied_seq']}, pending {fr['pending_events']}; "
          f"{len(primary)} live records "
          f"(+{ing.metrics['upserts']} event upserts, "
          f"{ing.metrics['tombstones']} tombstones)")
    print(f"query under freshness contract: {len(out['result'])} matches "
          f"at staleness {fr['staleness_s'] * 1e3:.1f} ms")

    print("\n== 5. interactive discovery (secondary indexes, DESIGN.md §11) ==")
    primary.attach_discovery()                  # sorted runs + trigrams
    hits = q_live.query("find_by_name", r"/f1\d\d$")
    print(f"find_by_name via {q_live.last_plan['route']} route: "
          f"{len(hits['result'])} matches "
          f"(index_lag={hits['freshness']['index_lag']})")
    cold = q_live.not_accessed_since(180 * 86400)
    print(f"cold-data window via {q_live.last_plan['route']} route: "
          f"{len(cold)} candidates")

    print("\n== 6. dashboards (clock pinned to the corpus epoch) ==")
    rep = scheduled_report(q_live, active_uids=list(range(16)), now=1.7e9)
    print(f"scheduled report at t={rep['generated_at']:.0f}: "
          f"{rep['counts']}")
    print(render_dashboard(primary, agg, k=3, now=1.7e9)
          .splitlines()[0])
    print("\nOK")


if __name__ == "__main__":
    main()
