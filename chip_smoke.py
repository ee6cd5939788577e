"""Smoke run of the Icicle index on one TPU chip, through the public
classes and with compiled Pallas kernels.

    python chip_smoke.py [--seed S] [--records N]     # one chip
    python chip_smoke.py --chips 4                    # 4-chip mesh phase only

One chip, in one process:

1. snapshot: a ``synth_filesystem`` namespace of ``--records`` files is
   routed through the hashshard kernel into a 4-shard
   ``ShardedPrimaryIndex``; the counting and aggregate pipelines run on
   the device (the aggregate step through the grouped-DDSketch kernel);
2. events: changelog windows (``mixed_workload`` with directory renames,
   then ``filebench_workload``) flow EventLog -> DurablePipeline ->
   EventIngestor in eager mode with the segstats and DDSketch kernels,
   with one checkpoint and a final drain;
3. queries: the Table-I dashboard mix through ``QueryService.
   query_batch`` (fused predeval kernel route), then ``stat``,
   ``find_by_glob`` and ``du``, each byte-compared with a plain scan
   engine over the same state.

Every kernel's device output is also compared with its jnp reference on
the chip. ``--chips 4`` runs only the shard_map counting and aggregate
steps over a data=4 mesh and compares them with the one-device
references. Timings printed are smoke timings of one cold run, not
metrics. The last stdout line is ``{"ok": true, "device": {...}}``; any
failed check exits non-zero. Without a TPU it exits non-zero at once.
"""
from __future__ import annotations

import argparse
import contextlib
import functools
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import events as ev
from repro.core import snapshot as snap
from repro.core.event_ingest import EventIngestor, IngestConfig
from repro.core.eventlog import EventLog
from repro.core.index import AggregateIndex, PrimaryIndex
from repro.core.metadata import TYPE_DIR, files_only, synth_filesystem
from repro.core.query import QueryEngine, pred_spec
from repro.core.query_service import QueryService
from repro.core.sharded_index import ShardedPrimaryIndex
from repro.core.stream_pipeline import DurablePipeline
from repro.kernels.hashshard import ops as hs_ops
from repro.kernels.hashshard.ref import encode_strings_np, hashshard_ref
from repro.kernels.predeval import ops as pk_ops
from repro.kernels.predeval import ref as pk_ref
from repro.kernels.segstats import ops as seg_ops
from repro.kernels.segstats.ref import segstats_ref
from repro.launch.compile_cache import configure_compile_cache
from repro.launch.mesh import make_mesh

#: the synthetic corpus epoch (``synth_filesystem``'s default ``now``):
#: every time-relative query resolves against it
NOW = 1.7e9
N_SHARDS = 4
#: event fids start above every snapshot file number, so event-born
#: subjects never collide with snapshot paths
EVENT_FID0 = 100_000_000
#: float32 sums accumulated in a different order than a float64 host
#: sum: relative error bound (n_blocks * 2^-24 for the kernels' blocked
#: accumulation over <= 8192 row blocks, rounded up)
SUM_RTOL = 1e-3


class SmokeFailure(AssertionError):
    pass


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def require_tpu() -> None:
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX platform {devs[0].platform!r}); "
              "this smoke run has no CPU fallback", file=sys.stderr)
        sys.exit(2)


@contextlib.contextmanager
def phase(name: str, timings: dict):
    print(f"== {name}", flush=True)
    t0 = time.perf_counter()
    yield
    timings[name] = time.perf_counter() - t0
    print(f"   {name}: smoke time {timings[name]:.1f} s", flush=True)


def same(a, b) -> bool:
    """Byte-for-byte equality of query results (arrays by dtype and
    content, everything else by value)."""
    if isinstance(a, np.ndarray) or isinstance(b, np.ndarray):
        return (isinstance(a, np.ndarray) and isinstance(b, np.ndarray)
                and a.dtype == b.dtype and np.array_equal(a, b))
    return a == b


def principal_names(pcfg: snap.PipelineConfig):
    return ([f"user:{i}" for i in range(pcfg.n_users)]
            + [f"group:{i}" for i in range(pcfg.n_groups)]
            + [f"dir:{i}" for i in range(pcfg.n_dirs)])


def corpus(records: int, seed: int):
    """The namespace and its pipeline rows: zipf owners over the
    pipeline's 256 users, lognormal sizes, ~40 files per directory."""
    pcfg = snap.PipelineConfig()
    table = synth_filesystem(records, n_users=pcfg.n_users,
                             n_groups=pcfg.n_groups,
                             n_dirs=max(64, records // 40), seed=seed,
                             now=NOW)
    rows, valid = snap.pad_rows(snap.preprocess(table, pcfg), 1024)
    return pcfg, table, rows, valid


def host_sums(pids, vals, mask, n):
    return np.bincount(pids, weights=np.asarray(vals, np.float64)
                       * np.asarray(mask, np.float64), minlength=n)[:n]


def check_sums(got, want, what: str) -> None:
    got = np.asarray(got, np.float64)
    err = np.abs(got - want) / np.maximum(np.abs(want), 1.0)
    check(float(err.max(initial=0.0)) <= SUM_RTOL,
          f"{what}: float32 sums off the float64 host sum by "
          f"{float(err.max(initial=0.0)):.3g} (> {SUM_RTOL})")


def check_aggregate(pcfg, rows, valid, state, ref, what: str) -> None:
    """Kernel sketch state vs the jnp reference: integer-valued fields
    exactly, sums against a float64 host sum of the same rows."""
    for k in ("counts", "zero_count", "count", "min", "max"):
        check(np.array_equal(np.asarray(state[k]), np.asarray(ref[k])),
              f"{what}: sketch field {k!r} differs from the jnp reference")
    streams = [(rows["uid_slot"], np.ones(len(valid))),
               (rows["gid_slot"], np.ones(len(valid)))]
    ds = rows["dir_slots"]
    streams += [(np.maximum(ds[:, i], 0), (ds[:, i] >= 0).astype(float))
                for i in range(ds.shape[1])]
    total = np.asarray(state["total"])
    for ai, attr in enumerate(snap.ATTRS):
        want = sum(host_sums(pid, rows[attr], m * valid, pcfg.n_principals)
                   for pid, m in streams)
        check_sums(total[:, ai], want, f"{what}: {attr} totals")


# ---------------------------------------------------------------------------
# phase 1: snapshot
# ---------------------------------------------------------------------------

def snapshot_phase(args, timings, report):
    with phase("corpus", timings):
        pcfg, table, rows, valid = corpus(args.records, args.seed)
        files = files_only(table)
        n = len(files)
        report["records"] = n
        print(f"   {n} files, {len(table) - n} directories")

    with phase("snapshot ingest (hashshard route)", timings):
        primary = ShardedPrimaryIndex(N_SHARDS)
        chunks = np.array_split(np.arange(n), -(-n // (1 << 20)))
        for rows_i in chunks:
            paths = files.paths[rows_i]
            check(len(paths) >= primary.kernel_route_min,
                  "snapshot chunk below the device-route threshold")
            h, _ = primary.route(paths)
            check(np.array_equal(h, files.path_hash[rows_i]),
                  "hashshard route disagrees with the host FNV hash")
            cols = {k: np.asarray(getattr(files, k)[rows_i], dt)
                    for k, dt in PrimaryIndex.STANDARD_COLUMNS.items()}
            cols["path_hash"] = h
            primary.upsert_batch(paths, cols, np.ones(len(paths), np.int64))
        check(len(primary) == n, "snapshot ingest lost records")
        report["routes"]["snapshot_batches_hashshard"] = len(chunks)
        # the compiled kernel vs its jnp reference on one routed batch
        enc, lens, _ = encode_strings_np(files.paths[chunks[0]],
                                         primary.route_width)
        h_k, s_k = hs_ops.hashshard_route(enc, lens, N_SHARDS)
        h_r, s_r = jax.jit(hashshard_ref, static_argnums=2)(
            jnp.asarray(enc), jnp.asarray(lens), N_SHARDS)
        check(np.array_equal(np.asarray(h_k), np.asarray(h_r))
              and np.array_equal(np.asarray(s_k), np.asarray(s_r)),
              "hashshard kernel differs from its jnp reference")
        print(f"   shard sizes {primary.shard_sizes().tolist()}")

    with phase("counting + aggregate pipelines", timings):
        mesh = make_mesh((1, 1), ("data", "model"))
        rows_d, valid_d = place_rows(rows, valid, mesh)
        counts = jax.jit(snap.make_counting_step(pcfg, mesh))(rows_d,
                                                              valid_d)
        counts_ref = jax.jit(snap.counting_local, static_argnums=0)(
            pcfg, rows_d, valid_d)
        check(np.array_equal(np.asarray(counts), np.asarray(counts_ref)),
              "counting step differs from counting_local")
        state = jax.jit(snap.make_aggregate_step(pcfg, mesh))(rows_d,
                                                              valid_d)
        state_ref = jax.jit(snap.aggregate_local, static_argnums=0)(
            pcfg, rows_d, valid_d)
        check_aggregate(pcfg, rows, valid, state, state_ref,
                        "aggregate step (ddsketch kernel)")
        agg = AggregateIndex()
        agg.from_sketch_state(pcfg.sketch, state, principal_names(pcfg))
        print(f"   {len(agg)} principals published")

    with phase("segstats kernel vs reference", timings):
        args_ = (jnp.asarray(rows["uid_slot"]), jnp.asarray(rows["shard_id"]),
                 jnp.asarray(rows["size"]), jnp.asarray(valid, jnp.float32))
        got = seg_ops.segstats(*args_, pcfg.n_principals, pcfg.n_shards)
        want = jax.jit(segstats_ref, static_argnums=(4, 5))(
            *args_, pcfg.n_principals, pcfg.n_shards)
        for k in ("counts", "min", "max"):
            check(np.array_equal(np.asarray(got[k]), np.asarray(want[k])),
                  f"segstats {k!r} differs from its jnp reference")
        check_sums(got["sum"], host_sums(rows["uid_slot"], rows["size"],
                                         valid, pcfg.n_principals),
                   "segstats sums")
    return pcfg, table, primary, agg, np.asarray(counts)


def place_rows(rows, valid, mesh):
    def put(x):
        spec = P("data", *([None] * (x.ndim - 1)))
        return jax.device_put(x, NamedSharding(mesh, spec))
    return {k: put(v) for k, v in rows.items()}, put(valid)


# ---------------------------------------------------------------------------
# phase 2: events
# ---------------------------------------------------------------------------

def events_phase(args, timings, report, pcfg, primary, agg, counts):
    with phase("events (EventLog -> DurablePipeline -> EventIngestor)",
               timings):
        ing = EventIngestor(IngestConfig(mode="eager"),
                            pcfg, primary, agg)
        # snapshot -> event handoff: the root fid resolves to /fs, the
        # rollup tree is seeded from the loaded records, the counting
        # matrix from the counting pipeline
        ing.register_tree({}, {0: "fs"}, {0: True})
        ing.seed_counts(counts)
        log = EventLog()
        pipe = DurablePipeline(log, ing, n_partitions=N_SHARDS,
                               batch_size=args.batch_events)
        stream = ev.EventStream(start_fid=EVENT_FID0)
        ev.mixed_workload(stream, args.batch_events * args.windows // 2,
                          seed=args.seed, rename_frac=0.02,
                          n_users=pcfg.n_users, n_groups=pcfg.n_groups)
        ev.filebench_workload(stream, args.batch_events * args.windows // 8,
                              args.batch_events * args.windows // 8,
                              seed=args.seed + 1, has_stat=1,
                              n_users=pcfg.n_users, n_groups=pcfg.n_groups)
        n_events = len(stream)
        renames = 0
        batches = 0
        with tempfile.TemporaryDirectory() as d:
            while len(stream):
                batch = stream.take(args.batch_events)
                renames += int(np.sum((batch["etype"] == ev.E_RENME)
                                      & (batch["is_dir"] == 1)))
                pipe.produce(batch, names=stream.take_names())
                batches += 1
                if batches % 2 == 0:
                    pipe.pump()
                if batches == args.windows // 2:
                    pipe.checkpoint(os.path.join(d, "ckpt.msgpack.zst"))
            pipe.drain()
        fresh = ing.freshness()
        check(fresh["applied_seq"] == n_events
              and fresh["pending_events"] == 0
              and fresh["log_lag"] == 0,
              f"events not all visible after drain: {fresh}")
        check(batches >= 8 and renames > 0,
              "event phase ran too few windows or no directory renames")
        check(pipe.metrics["checkpoints"] == 1, "checkpoint did not run")
        report["events"] = n_events
        report["routes"]["event_batches"] = batches
        report["routes"]["dir_renames"] = renames
        print(f"   {n_events} events in {batches} batches "
              f"({renames} dir renames), watermark {fresh['applied_seq']}, "
              f"{len(primary)} live records")
        # the counting matrix (snapshot seed + segstats deltas) matches
        # the live index's owners exactly
        live_uid = primary.live()["uid"].astype(np.int64) % pcfg.n_users
        check(np.array_equal(ing.counts[:pcfg.n_users].sum(axis=1),
                             np.bincount(live_uid, minlength=pcfg.n_users)),
              "per-user counts drift from the live index")
    return ing


# ---------------------------------------------------------------------------
# phase 3: queries
# ---------------------------------------------------------------------------

def dashboard_mix():
    """Table-I dashboard panels: the five predicate families over seven
    thresholds each, plus four world-writable panels (32 programs)."""
    mix = []
    for v in range(7):
        months = (3 + 2 * v) * 30 * 86400
        mix += [("not_accessed_since", (months,), {}),
                ("large_cold_files", (10.0 ** (6 + v / 2), months), {}),
                ("past_retention", (2 * months,), {}),
                ("owned_by_deleted_users", (list(range(4 + 4 * v)),), {})]
    return mix + [("world_writable", (), {})] * 4


def queries_phase(timings, report, pcfg, table, primary, agg, ing):
    oracle = QueryEngine(primary, agg, now=NOW, use_kernels=False)
    mix = dashboard_mix()
    with phase("dashboard query_batch (predeval route)", timings):
        svc = QueryService(primary, agg, ingestor=ing, now=NOW)
        res = svc.query_batch([{"name": n, "args": a, "kw": k}
                               for n, a, k in mix])
        for (name, a, k), r in zip(mix, res):
            check(same(r["result"], getattr(oracle, name)(*a, **k)),
                  f"query_batch {name}{a} differs from the scan")
        with svc.snapshot() as s:
            batch = s.engine.select_many(mix, now=NOW)
            plan = s.engine.last_plan
            check(plan["route"] == "kernel" and plan["batched"] == len(mix),
                  f"dashboard batch did not take the kernel route: {plan}")
            routes = set()
            for (name, a, k), r in zip(mix, batch):
                got = getattr(s.engine, name)(*a, **k)
                routes.add(s.engine.last_plan["route"])
                check(same(got, r), f"{name}{a}: single != batched")
            check(routes == {"kernel"}, f"predicate routes taken: {routes}")
        report["routes"]["predicate_queries"] = "kernel"
        print(f"   {len(mix)} panels, kernel route, "
              f"{plan['candidates']} candidates verified")

    with phase("predeval kernel vs references", timings):
        progs = pk_ref.stack_programs(
            [pk_ref.compile_program(pred_spec(name, a, k, NOW))
             for name, a, k in mix])
        ref_fn = jax.jit(functools.partial(pk_ref.predeval_ref,
                                           has_set=progs.has_set))
        for sh in primary.shards:
            n = len(sh.slot_map)
            arena = pk_ops.pack_arena(sh.columns, sh.alive, n)
            words = pk_ops.predeval_words(arena, progs)
            words_ref = np.asarray(ref_fn(
                arena.fcols, arena.icols, arena.alive,
                *(jnp.asarray(x) for x in (progs.ops, progs.lo, progs.hi,
                                           progs.msk, progs.setrows,
                                           progs.setcol, progs.setvals))))
            words_host = pk_ref.predeval_host(
                np.asarray(arena.fcols), np.asarray(arena.icols),
                np.asarray(arena.alive), progs)
            check(np.array_equal(words, words_ref)
                  and np.array_equal(words, words_host),
                  "predeval bitmaps differ from the jnp / host references")
        print(f"   {progs.k} programs x {N_SHARDS} shard arenas "
              f"({arena.n_pad} rows each) bit-identical")

    with phase("stat / find_by_glob / du", timings):
        probes = [str(table.paths[-1]), "/fs/no/such/file"]
        probes += [str(p) for p in primary.live_paths()[-3:]]
        for p in probes:
            got = svc.query("stat", p)["result"]
            check(same(got, oracle.stat(p)), f"stat {p} differs")
        check(any(svc.query("stat", p)["result"] is not None
                  for p in probes), "stat found no probe")
        for pat in ("*/f12??", "/fs/d1/*", "*/d*/f1000*"):
            check(same(svc.query("find_by_glob", pat)["result"],
                       oracle.find_by_glob(pat)), f"find_by_glob {pat}")
        du = svc.query("du", "/fs", 2)["result"]
        check(same(du, oracle.du("/fs", 2)), "du /fs differs")
        live = primary.live()
        n_files = int(np.sum((live["type"] != TYPE_DIR)
                             & np.char.startswith(live["path"].astype(str),
                                                  "/fs/")))
        check(du["file_count"] == n_files,
              f"du /fs counts {du['file_count']} of {n_files} files")
        print(f"   du /fs: {du['file_count']} files, "
              f"{du['total_bytes']} bytes")
        svc.close()


# ---------------------------------------------------------------------------
# four chips: the shard_map pipeline steps over a data=4 mesh
# ---------------------------------------------------------------------------

def mesh_phase(args, timings, report, n_dev: int):
    devs = jax.devices()
    check(len(devs) >= n_dev, f"{n_dev} devices asked, {len(devs)} present")
    with phase("corpus", timings):
        pcfg, _, rows, valid = corpus(args.records, args.seed)
        n_rows = len(valid)
        report["records"] = int(valid.sum())
        print(f"   {n_rows // n_dev} rows per device")
    with phase(f"counting + aggregate steps on data={n_dev}", timings):
        mesh = make_mesh((n_dev, 1), ("data", "model"))
        rows_d, valid_d = place_rows(rows, valid, mesh)
        for x in (valid_d, rows_d["size"]):
            shard_devs = {s.device for s in x.addressable_shards}
            check(len(shard_devs) == n_dev
                  and all(s.data.shape[0] == n_rows // n_dev
                          for s in x.addressable_shards),
                  "row inputs do not span the mesh devices")
        counts = jax.jit(snap.make_counting_step(pcfg, mesh))(rows_d,
                                                              valid_d)
        state = jax.jit(snap.make_aggregate_step(pcfg, mesh))(rows_d,
                                                              valid_d)
        for out in (counts, state["counts"], state["total"]):
            check(len({s.device for s in out.addressable_shards}) == n_dev,
                  "pipeline outputs do not span the mesh devices")
    with phase("one-device references", timings):
        one = jax.devices()[0]
        rows_1 = {k: jax.device_put(v, one) for k, v in rows.items()}
        valid_1 = jax.device_put(valid, one)
        counts_ref = jax.jit(snap.counting_local, static_argnums=0)(
            pcfg, rows_1, valid_1)
        check(np.array_equal(np.asarray(counts), np.asarray(counts_ref)),
              "mesh counting step differs from counting_local")
        state_ref = jax.jit(snap.aggregate_local, static_argnums=0)(
            pcfg, rows_1, valid_1)
        check_aggregate(pcfg, rows, valid, state, state_ref,
                        f"mesh aggregate step on {n_dev} chips")
    report["routes"]["mesh"] = f"data={n_dev}"


# ---------------------------------------------------------------------------

def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--records", type=int, default=4_000_000,
                    help="snapshot files (default 4M: 2^20 per shard)")
    ap.add_argument("--windows", type=int, default=10,
                    help="event batches produced (>= 8)")
    ap.add_argument("--batch-events", type=int, default=8192)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the data=4 mesh pipeline phase")
    return ap.parse_args(argv)


def main(argv=None) -> None:
    args = parse_args(argv)
    require_tpu()
    configure_compile_cache()
    timings: dict = {}
    report: dict = {"routes": {}}
    if args.chips == 4:
        mesh_phase(args, timings, report, 4)
    else:
        pcfg, table, primary, agg, counts = snapshot_phase(args, timings,
                                                           report)
        ing = events_phase(args, timings, report, pcfg, primary, agg,
                           counts)
        queries_phase(timings, report, pcfg, table, primary, agg, ing)
    dev = jax.devices()[0]
    print("smoke summary: " + json.dumps(
        {"records": report.get("records"), "events": report.get("events"),
         "routes": report["routes"],
         "smoke_seconds": {k: round(v, 1) for k, v in timings.items()}}))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
