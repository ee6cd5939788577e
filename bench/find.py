"""Driver for ``kind: find`` configurations: IO500's find phase over the
namespace its mdtest phases leave, served by the program's
``QueryService``.

Set-up generates the namespace (``mdtest``), loads every file through
the routed primary workflow (``deploy.load_chunk``: the hashshard route,
then ``upsert_batch``) in ``chunk``-record chunks, builds a
``QueryService`` over the index and runs ``warm`` untimed finds, which
compile the predicate kernel for each arena shape and pack the arenas.

The window then sends ``QueryService.query("find", name, size, T_k)``
from one client, back to back (closed loop). Each ``T_k`` lies before
the timestamp file, so every find has IO500's answer; the ``T_k`` are
distinct (one second apart), so every find misses the result cache and
runs the whole route. The window ends at the first find boundary after
``--seconds``; all of that time counts. ``records_per_s`` is IO500's
find rate: namespace records searched per second. Afterwards every
answer is compared with ``find_reference``.
"""
from __future__ import annotations

import time
from typing import Dict

import numpy as np

import deploy
import find_reference
import harness
import mdtest
import roofline_predeval

#: the columns the find's predicate program tests (size, mtime)
FIND_COLUMNS = 2
ROUTE_KEY = "query_route_seconds|route=kernel:count"


def run(cell: Dict, seed: int, seconds: float, trace: bool, spans,
        tracer, compiles, log, control: str = "") -> Dict:
    import jax
    from repro.core.query import QueryEngine
    if "find" not in QueryEngine.QUERY_METHODS:
        raise RuntimeError("the program has no find query "
                           "(QueryEngine.QUERY_METHODS)")
    from repro.core.query_service import QueryService
    from repro.core.sharded_index import ShardedPrimaryIndex
    cfg, mix = cell["cfg"], cell["mix"]
    name, size = mix["name"], float(mix["size"])

    t = time.perf_counter()
    ns = mdtest.namespace_for(cfg["namespace"], seed)
    n = len(ns)
    log(f"setup: namespace {n} files ({ns.n_hard} hard) under "
        f"{ns.run_dir} ({time.perf_counter() - t:.1f} s)")

    t = time.perf_counter()
    ix = cfg["index"]
    primary = ShardedPrimaryIndex(ix["n_shards"])
    spans_off = harness.Spans()
    for lo, hi in deploy.chunks(n, int(ix["chunk"])):
        deploy.load_chunk(primary, ns.paths, ns.columns, lo, hi,
                          ix["load_version"], spans_off)
    svc = QueryService(primary)
    lo_s, hi_s = mix["newer_before_stamp_s"]
    t_0 = ns.stamp - float(np.random.default_rng([seed, 61]).integers(
        lo_s, hi_s))
    warm = int(mix["warm"])
    for k in range(warm):
        svc.query("find", name, size, t_0 - k)
    log(f"setup: routed load of {n} records, {warm} warm finds "
        f"({time.perf_counter() - t:.1f} s); shard rows "
        f"{primary.shard_sizes().tolist()}")

    answers = []               # (T, paths)
    tracer.start()
    before = harness.counters()
    spans.on = True
    compiles.on = True
    t0 = time.perf_counter()
    t_end = t0 + seconds
    k = warm
    with jax.profiler.TraceAnnotation("bench.window"):
        while True:
            newer = t_0 - k
            with spans("find"):
                res = svc.query("find", name, size, newer)
            answers.append((newer, res["result"]))
            k += 1
            if time.perf_counter() >= t_end:
                break
    t_close = time.perf_counter()
    spans.on = False
    compiles.on = False
    after = harness.counters()
    tracer.stop()
    window = t_close - t0
    mem = harness.memory_peak()
    counters = harness.delta(after, before)
    log(f"window: {len(answers)} finds of {n} records in {window:.3f} s, "
        f"{compiles.n} compiles inside the window; "
        f"{len(answers[-1][1])} files found")

    checks = compare(ns, name, size, answers, control, log)
    checks["route_not_kernel"] = (
        len(answers) - int(round(counters.get(ROUTE_KEY, 0.0))), 0)
    checks["window_compiles"] = (compiles.n, 0)
    work = {"predeval": len(answers) * roofline_predeval.predeval_bytes(
        n, FIND_COLUMNS)}
    ctx = {"counters": counters, "spans": dict(spans.total),
           "window_s": window, "work": work}
    return {"t_first": t0, "e2e": {"records_per_s": n * len(answers)
                                   / window},
            "ctx": ctx, "memory": mem, "checks": checks,
            "attempted": len(answers), "failed": 0}


def compare(ns, name, size, answers, control, log) -> Dict:
    """``find_mismatch``: answers whose paths, as a set and a count, are
    not exactly the reference's (the index answers in its own row
    order; the CPU tests pin that order). With ``control="bf16"`` the
    reference computed at bfloat16 stands in for the program."""
    t = time.perf_counter()
    ref = find_reference.Find(ns.paths, ns.columns["size"],
                              ns.columns["mtime"], name, size)
    if control == "bf16":
        import jax.numpy as jnp
        low = find_reference.Find(ns.paths, ns.columns["size"],
                                  ns.columns["mtime"], name, size,
                                  dtype=jnp.bfloat16)
        answers = [(newer, low(newer)) for newer, _ in answers]
    want: Dict = {}
    bad = 0
    for newer, got in answers:
        key = np.float32(newer)
        if key not in want:
            want[key] = set(ref(newer))
        w = want[key]
        if len(got) != len(w) or set(got) != w:
            bad += 1
    log(f"check: {len(answers)} finds compared with the reference "
        f"({time.perf_counter() - t:.1f} s)")
    return {"find_mismatch": (bad, 0)}
