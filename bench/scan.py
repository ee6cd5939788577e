"""Driver for ``kind: scan`` configurations: a periodic full re-scan of
a namespace into an index that already holds it.

Set-up generates the namespace and its pipeline rows, loads every file
at version 1 through the routed primary workflow, and runs the counting
and aggregate workflows once (compiling their chunk shape). The window
then re-scans pass after pass at versions 2, 3, ...: each chunk of
``chunk`` records goes through the paper's three Table V workflows, in
order and back to back (closed loop):

- primary: ``ShardedPrimaryIndex.route`` (the hashshard kernel) and
  ``upsert_batch`` at the pass's version;
- counting: ``snapshot.make_counting_step``;
- aggregate: ``snapshot.make_aggregate_step`` (the DDSketch kernel),
  merged into the window's state with the program's ``ddsketch.merge``.

A share ``change_frac`` of the files carries a new size and mtime in
each pass (two alternating sets drawn from the seed). The window ends at
the first chunk boundary after ``--seconds``; all of that time counts.
Afterwards the route's hashes, sampled index records, the counting
matrix and the sketch state are compared with ``reference``.
"""
from __future__ import annotations

import time
from typing import Dict, List

import numpy as np

import corpus
import deploy
import harness
import reference
import roofline

ATTRS = ("size", "atime", "ctime", "mtime")


def variants(files, rows, frac: float, seed: int, now: float) -> List[Dict]:
    """Two alternating re-scan views: the same files, ``frac`` of them
    (the same count for every seed) grown in size and modified within
    the last day."""
    out = []
    n = len(files)
    for v in range(2):
        rng = np.random.default_rng([seed, 43 + v])
        on = np.zeros(n, bool)
        on[rng.choice(n, int(round(frac * n)), replace=False)] = True
        size = rows["size"].copy()
        mtime = rows["mtime"].copy()
        size[on] = (files.size[on] * (1.0 + rng.random(int(on.sum())))
                    ).astype(np.float32)
        mtime[on] = (now - rng.exponential(86400.0, int(on.sum()))
                     ).astype(np.float32)
        ctime = rows["ctime"].copy()
        ctime[on] = mtime[on]
        out.append({"size": size, "mtime": mtime, "ctime": ctime,
                    "changed": int(on.sum())})
    return out


def run(cell: Dict, seed: int, seconds: float, trace: bool, spans,
        tracer, compiles, log, control: str = "") -> Dict:
    import jax
    cfg, mix = cell["cfg"], cell["mix"]
    from repro.core.sharded_index import ShardedPrimaryIndex

    t = time.perf_counter()
    ns = corpus.namespace_for(cfg["namespace"], seed)
    files = ns.files()
    rows = corpus.pipeline_rows(ns, cfg["pipeline"])
    n = len(files)
    cols = files.index_columns()
    views = variants(files, rows, float(mix["change_frac"]), seed,
                     float(cfg["namespace"]["now"]))
    log(f"setup: namespace {n} files, {ns.n_dirs} dirs "
        f"({time.perf_counter() - t:.1f} s)")

    t = time.perf_counter()
    ix = cfg["index"]
    chunk = int(ix["chunk"])
    primary = ShardedPrimaryIndex(ix["n_shards"])
    spans_off = harness.Spans()
    for lo, hi in deploy.chunks(n, chunk):
        rows["path_hash"][lo:hi] = deploy.load_chunk(
            primary, files.paths, cols, lo, hi, ix["load_version"],
            spans_off)
    pcfg = deploy.pipeline_config(cfg["pipeline"])
    wf = deploy.Workflows(pcfg, chunk)
    rd, vd = wf.place(rows, 0, min(chunk, n))
    st = wf.merge(wf.init(), wf.aggregate(rd, vd))
    jax.block_until_ready(wf.merge(st, wf.aggregate(rd, vd)))
    np.asarray(wf.count(rd, vd))
    log(f"setup: first load and workflow compile "
        f"({time.perf_counter() - t:.1f} s)")

    bounds = deploy.chunks(n, chunk)
    done: List = []            # (pass, chunk index, variant, hashes)
    counts = np.zeros((pcfg.n_principals, pcfg.n_shards), np.float64)
    state = wf.init()
    work = {"hashshard": 0.0, "ddsketch": 0.0}
    path_len = None
    if trace:
        path_len = np.fromiter((len(p.encode()) for p in files.paths),
                               np.int64, n)

    tracer.start()
    before = harness.counters()
    spans.on = True
    compiles.on = True
    t0 = time.perf_counter()
    t_end = t0 + seconds
    p, c = 1, 0
    with jax.profiler.TraceAnnotation("bench.window"):
        while True:
            lo, hi = bounds[c]
            v = (p - 1) % 2
            view = views[v]
            ccols = dict(cols)
            ccols.update(size=view["size"], mtime=view["mtime"],
                         ctime=view["ctime"])
            h = deploy.load_chunk(primary, files.paths, ccols, lo, hi,
                                  ix["load_version"] + p, spans)
            crow = dict(rows)
            crow.update(size=view["size"], mtime=view["mtime"],
                        ctime=view["ctime"])
            rows["path_hash"][lo:hi] = h
            rd, vd = wf.place(crow, lo, hi)
            with spans("count"):
                counts += np.asarray(wf.count(rd, vd), np.float64)
            with spans("aggregate"):
                state = jax.block_until_ready(
                    wf.merge(state, wf.aggregate(rd, vd)))
            done.append((p, c, v, h))
            if trace:
                work["hashshard"] += roofline.hashshard_bytes(
                    path_len[lo:hi])
                work["ddsketch"] += roofline.ddsketch_bytes(
                    hi - lo, 2 + pcfg.dir_max - pcfg.dir_min + 1,
                    len(ATTRS), pcfg.n_principals, pcfg.sketch.n_buckets)
            c += 1
            if c == len(bounds):
                p, c = p + 1, 0
            if time.perf_counter() >= t_end:
                break
    t_close = time.perf_counter()
    spans.on = False
    compiles.on = False
    after = harness.counters()
    tracer.stop()
    window = t_close - t0
    records = sum(bounds[c][1] - bounds[c][0] for _, c, _, _ in done)
    mem = harness.memory_peak()
    log(f"window: {len(done)} chunks ({records} records) in "
        f"{window:.3f} s, {compiles.n} compiles inside the window")
    log("window: host spans " + ", ".join(
        f"{k} {v:.3f} s" for k, v in sorted(spans.total.items())))

    checks = compare(pcfg, files, rows, cols, views, bounds, done, primary,
                     counts, state, ix, seed, control, log)
    ctx = {"counters": harness.delta(after, before),
           "spans": dict(spans.total), "window_s": window, "work": work}
    return {"t_first": t0, "e2e": {"records_per_s": records / window},
            "ctx": ctx, "memory": mem, "checks": checks,
            "attempted": len(done), "failed": 0}


def compare(pcfg, files, rows, cols, views, bounds, done, primary, counts,
            state, ix, seed, control, log) -> Dict:
    t = time.perf_counter()
    rng = np.random.default_rng([seed, 53])
    sk = pcfg.sketch
    checks = {}
    # the route's hashes, on two chunks drawn from the seed
    bad = 0
    for j in rng.choice(len(done), min(2, len(done)), replace=False):
        _, c, _, h = done[j]
        lo, hi = bounds[c]
        bad += int((np.asarray(h, np.uint32)
                    != reference.fnv1a(files.paths[lo:hi].tolist())).sum())
    checks["hash_mismatch"] = (bad, 0)

    # sampled records of every completed chunk at its latest pass
    latest = {}
    for p, c, v, _ in done:
        latest[c] = (p, v)
    bad = 0
    for c, (p, v) in latest.items():
        lo, hi = bounds[c]
        for i in rng.integers(lo, hi, int(ix["sample_per_chunk"])):
            got = primary.lookup(str(files.paths[i]))
            want = {"size": float(views[v]["size"][i]),
                    "mtime": float(views[v]["mtime"][i]),
                    "ctime": float(views[v]["ctime"][i]),
                    "atime": float(cols["atime"][i]),
                    "uid": int(cols["uid"][i]), "gid": int(cols["gid"][i]),
                    "mode": int(cols["mode"][i]),
                    "version": int(ix["load_version"]) + p}
            if got is None or any(got.get(k) != x for k, x in want.items()):
                bad += 1
    checks["index_mismatch"] = (bad, 0)

    # the counting matrix and the sketch state over the completed chunks
    want_c = np.zeros_like(counts)
    ref = None
    per = {}
    for _, c, v, _ in done:
        per[(c, v)] = per.get((c, v), 0) + 1
    for (c, v), k in per.items():
        lo, hi = bounds[c]
        r = {key: val[lo:hi] for key, val in rows.items()}
        r.update({a: views[v][a][lo:hi] for a in ("size", "mtime", "ctime")})
        want_c += k * reference.counting(r, pcfg.n_principals, pcfg.n_shards)
        s = reference.sketch(r, ATTRS, pcfg.n_principals, sk.alpha,
                             sk.n_buckets, sk.offset)
        ref = reference.merge(ref, {key: (val * k if key not in
                                          ("min", "max") else val)
                                    for key, val in s.items()})
    if control == "bf16":
        state = _control_state(per, bounds, rows, views, pcfg)
    checks["count_mismatch"] = (int((np.asarray(counts) != want_c).sum()), 0)
    got = {k: np.asarray(v, np.float64) for k, v in state.items()}
    ints = 0
    for k in ("zero_count", "count", "min", "max"):
        ints += int((got[k] != ref[k]).sum())
    checks["sketch_int_mismatch"] = (ints, 0)
    checks["sketch_shift"] = (reference.bucket_shift(got["counts"],
                                                     ref["counts"]), 0.005)
    rel = np.abs(got["total"] - ref["total"]) / np.maximum(
        np.abs(ref["total"]), 1.0)
    checks["total_rel_err"] = (float(rel.max(initial=0.0)), 1e-5)
    log(f"check: hashes, {len(latest)} chunks of records, counts and "
        f"sketches compared ({time.perf_counter() - t:.1f} s)")
    return checks


def _control_state(per, bounds, rows, views, pcfg) -> Dict:
    """The control in the program's place: the reference aggregate with
    every value rounded to bfloat16 first."""
    import jax.numpy as jnp
    sk = pcfg.sketch
    out = None
    for (c, v), k in per.items():
        lo, hi = bounds[c]
        r = {key: val[lo:hi] for key, val in rows.items()}
        r.update({a: views[v][a][lo:hi] for a in ("size", "mtime", "ctime")})
        s = reference.sketch(r, ATTRS, pcfg.n_principals, sk.alpha,
                             sk.n_buckets, sk.offset,
                             value_dtype=jnp.bfloat16)
        out = reference.merge(out, {key: (val * k if key not in
                                          ("min", "max") else val)
                                    for key, val in s.items()})
    return out
