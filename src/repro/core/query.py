"""Query engine over the dual index — every representative query from
paper Table I, as vectorized predicates on the primary index plus direct
lookups on the aggregate index.

This is the programmatic surface the paper's web interface (graphical
query builder / raw regex mode / summary templates) sits on.

The engine is index-shape agnostic: ``primary`` may be the monolithic
``PrimaryIndex`` or a ``sharded_index.ShardedPrimaryIndex``. Scans read
the schema-stable ``live()`` view — on a sharded primary that is a
scatter-gather (per-shard views fanned out and merged); point lookups
(``stat``) route to the single owning shard (DESIGN.md §8).

Consistency semantics (paper §V-C; DESIGN.md §6.3): each query reads a
``live()`` view materialized at call time, so one query is internally
consistent — it never mixes a record's pre- and post-update columns. Two
successive queries may straddle an event-ingest apply and disagree;
callers that care attach the freshness watermark via ``freshness()`` /
``query()``, which reports the changelog seq the read data reflects and
how many events are still buffered behind it (nonzero only in the
ingestor's ``buffered`` mode).
"""
from __future__ import annotations

import fnmatch
import re
import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from repro.core import discovery as disc
from repro.core import hierarchy as hier
from repro.core.index import AggregateIndex, PrimaryIndex
from repro.core.telemetry import resolve as _resolve_tel
from repro.kernels.predeval import names as pk_names
from repro.kernels.predeval import ops as pk_ops
from repro.kernels.predeval import ref as pk_ref

#: the queries the planner can express as predicate lists over the
#: primary arenas — exactly the ones with a ``_plan_select`` route
PREDICATE_QUERIES = frozenset({
    "world_writable", "not_accessed_since", "large_cold_files",
    "owned_by_deleted_users", "past_retention",
})

#: predicate queries whose cutoffs derive from the query clock: their
#: answers change with wall time even at an unchanged watermark, so
#: the serving tier folds the resolved clock into their cache keys
TIME_RELATIVE = frozenset({
    "not_accessed_since", "large_cold_files", "past_retention",
})

#: queries answered from the subtree-rollup tree (DESIGN.md §14) when
#: an exact HierarchyIndex is attached, with a brute-force scan over
#: ``live()`` as the byte-identical fallback. The serving tier folds
#: the hierarchy's apply epoch into their cache keys — their answers
#: move with structure changes the primary watermark alone may miss.
HIER_QUERIES = frozenset({
    "du", "subtree_summary", "hot_directories",
})


def _bind(args: Tuple, kw: Dict, *names: str) -> List:
    """Bind one value per parameter name from (*args, **kw), no
    defaults, no extras — TypeError mirrors what calling the query
    method itself would raise."""
    if len(args) > len(names) or set(kw) - set(names[len(args):]):
        raise TypeError("bad query arguments")
    vals = list(args)
    for nm in names[len(args):]:
        if nm not in kw:
            raise TypeError(f"missing query argument {nm!r}")
        vals.append(kw[nm])
    return vals


def pred_spec(name: str, args: Tuple, kw: Dict,
              now: float) -> Optional[List[Tuple[str, str, object]]]:
    """The predicate list a named Table-I query evaluates — the same
    tuples its method hands ``_plan_select`` — with time-relative
    cutoffs resolved against the CALLER's ``now``. None when ``name``
    is not a predicate query or the arguments do not bind (the caller
    then dispatches the method directly and lets it raise naturally).
    Shared by ``select_many`` and the serving tier's time-pinned
    execution + cache keying."""
    if name not in PREDICATE_QUERIES:
        return None
    try:
        if name == "world_writable":
            _bind(args, kw)
            return [("mode", "mask", 0o002)]
        if name == "not_accessed_since":
            (seconds,) = _bind(args, kw, "seconds")
            return [("atime", "lt", now - float(seconds))]
        if name == "large_cold_files":
            min_size, idle = _bind(args, kw, "min_size", "idle_seconds")
            return [("size", "gt", min_size),
                    ("atime", "lt", now - float(idle))]
        if name == "owned_by_deleted_users":
            (uids,) = _bind(args, kw, "active_uids")
            return [("uid", "notin", list(uids))]
        if name == "past_retention":
            (ret,) = _bind(args, kw, "retention_seconds")
            return [("mtime", "lt", now - float(ret))]
    except TypeError:
        return None
    return None


def _shard_rows(sh) -> int:
    """Rows a scan of this shard covers: ``snapshot.n`` on a pinned
    view, ``len(slot_map)`` (assigned slots) on a live index."""
    n = getattr(sh, "n", None)
    if n is not None:
        return int(n)
    return len(sh.slot_map)


def resolve_now(now) -> float:
    """One clock-resolution rule for every ``now`` knob (QueryEngine,
    the dashboard renderers): None reads ``time.time`` at call time, a
    float pins a deterministic clock, a callable supplies your own."""
    if now is None:
        return time.time()
    return float(now()) if callable(now) else float(now)


def merge_freshness(marks: Sequence[Dict[str, float]]
                    ) -> Optional[Dict[str, float]]:
    """Combine per-partition watermarks into the deployment-wide one: a
    reader is only as fresh as its STALEST partition, so ``applied_seq``
    is the min over sources, ``staleness_s`` the max, and pending events
    sum (paper §IV-B4: one monitor/ingestor per MDT or index shard)."""
    marks = [m for m in marks if m]
    if not marks:
        return None
    return {
        "mode": "+".join(sorted({str(m.get("mode")) for m in marks})),
        # the required trio defaults like every later key: a mark from a
        # layer that only exports lag fields (e.g. a policy engine or a
        # bare replication tier) must degrade the merge, not KeyError it.
        # Missing applied_seq pins the deployment watermark at 0 — the
        # conservative "I can't vouch for anything newer" reading
        "applied_seq": min(m.get("applied_seq", 0) for m in marks),
        "pending_events": sum(m.get("pending_events", 0) for m in marks),
        "staleness_s": max(m.get("staleness_s", 0.0) for m in marks),
        "applied_batches": sum(m.get("applied_batches", 0) for m in marks),
        # a deployment is only as reconciled as its LEAST-recently
        # reconciled partition (0.0 = some partition never was)
        "reconciled_at": min(m.get("reconciled_at", 0.0) for m in marks),
        # uncommitted events still sitting in the durable log sum across
        # partitions, like pending_events (DESIGN.md §10.4; 0 on
        # direct-fed ingestors or marks predating the pipeline)
        "log_lag": sum(m.get("log_lag", 0) for m in marks),
        # primary mutations not yet reflected in queryable discovery
        # state (DESIGN.md §11.3; 0 = accelerated queries are exact,
        # also 0 when no discovery index is attached)
        "index_lag": sum(m.get("index_lag", 0) for m in marks),
        # subtree-rollup health (DESIGN.md §14): deferred propagation
        # work sums across partitions; the deployment's rollup route is
        # exact only if EVERY partition's tree is (marks predating the
        # rollup layer count as inexact, forcing the scan fallback)
        "rollup_dirty": sum(m.get("rollup_dirty", 0) for m in marks),
        "rollup_exact": all(m.get("rollup_exact", False) for m in marks),
        # replicated read tier (core/replication.py, DESIGN.md §15):
        # events applied on the leader but not yet on the laggiest
        # follower — a deployment's stale-tolerant reads trail by its
        # WORST replica, so the merge takes the max (0 = no replicas or
        # all caught up; marks predating replication count as 0)
        "replica_lag": max(m.get("replica_lag", 0) for m in marks),
        "sources": len(marks),
    }


class QueryEngine:
    def __init__(self, primary: PrimaryIndex, aggregate: AggregateIndex,
                 now=None, ingestor=None,
                 use_kernels: bool = True,
                 hierarchy=None, telemetry=None):
        """``ingestor``: optional event_ingest.EventIngestor (duck-typed —
        anything with ``freshness()``) whose watermark stamps results. A
        list/tuple of ingestors (e.g. one per MDT feeding a sharded
        primary) min-merges into one watermark via merge_freshness.

        ``now``: the clock the time-window predicates
        (``not_accessed_since`` / ``large_cold_files`` /
        ``past_retention``) evaluate against. Default None means
        ``time.time`` read PER QUERY — a long-lived engine must not
        freeze its notion of "now" at construction, or cold-data windows
        silently drift stale. Pass a float to pin a deterministic clock
        (tests, replaying historical scans) or any callable to supply
        your own.

        ``use_kernels``: route predicate queries through the fused
        predicate kernel (DESIGN.md §13) when the discovery index
        cannot serve them; False pins the pure-numpy scan.

        ``hierarchy``: optional hierarchy.HierarchyIndex serving the
        subtree-rollup queries (``du`` / ``subtree_summary`` /
        ``hot_directories``). None auto-adopts ``ingestor.hierarchy``
        when a single ingestor is attached; without one, those queries
        fall back to the brute-force scan over ``live()``."""
        self.primary = primary
        self.aggregate = aggregate
        self._now = time.time if now is None else now
        self.ingestor = ingestor
        self.use_kernels = use_kernels
        if hierarchy is None and ingestor is not None \
                and not isinstance(ingestor, (list, tuple)):
            hierarchy = getattr(ingestor, "hierarchy", None)
        self.hierarchy = hierarchy
        #: per-(shard position) device arena cache keyed by mutation
        #: epoch + row count: {si: ((epoch, n), Arena)}. Entries for a
        #: pinned snapshot engine never churn; on a live engine each
        #: mutation batch invalidates by key mismatch. Plain dict ops
        #: are atomic under the GIL — concurrent readers at worst
        #: rebuild the same immutable slab twice.
        self._arena_cache: Dict[int, Tuple] = {}
        #: the find's basename columns, cached like the arenas and
        #: built only when a find with a usable literal arrives
        self._names_cache: Dict[int, Tuple] = {}
        # per-thread plan records: concurrent readers sharing one
        # engine (the serving tier admits N at once) must not observe
        # each other's routing decisions
        self._plan_tls = threading.local()
        # route-cascade instruments, families bound once (labels() on a
        # hot path is one dict hit)
        self.telemetry = _resolve_tel(telemetry)
        self._h_route_s = self.telemetry.histogram(
            "query_route_seconds",
            "predicate-query latency by chosen route",
            labels=("route",))
        self._c_fallback = self.telemetry.counter(
            "query_discovery_fallback_total",
            "planner declines by reason",
            labels=("reason",))
        self._c_candidates = self.telemetry.counter(
            "query_candidates_total",
            "rows a query kept at each stage: the kernel's bitmap, the "
            "name prefilter, the exact verify, the name match",
            labels=("query", "stage"))
        self._c_prefilter = self.telemetry.counter(
            "query_name_prefilter_total",
            "finds by where their basename prefilter ran: on the device "
            "(kernel route, a literal in the glob) or on the host alone",
            labels=("route",))
        # stage spans of the kernel route and the find's name match,
        # bound once; each is a leaf
        self._span_pack = self.telemetry.span("query.arena.pack")
        self._span_device = self.telemetry.span("query.select.device")
        self._span_unpack = self.telemetry.span("query.select.unpack")
        self._span_verify = self.telemetry.span("query.select.verify")
        self._span_name = self.telemetry.span("query.find.name")

    @property
    def now(self) -> float:
        """The query clock: re-read per access when callable-backed."""
        return resolve_now(self._now)

    @now.setter
    def now(self, value) -> None:
        self._now = value

    # -- freshness (paper's consistency/latency/freshness knobs) --------------

    def freshness(self) -> Optional[Dict[str, float]]:
        """Watermark of the data this engine reads: highest applied
        changelog seq, pending (buffered, not yet visible) events, and
        staleness seconds. None when no event ingestor is attached
        (pure-snapshot deployments). Multiple ingestors min-merge —
        freshness is the min watermark over partitions."""
        if self.ingestor is None:
            return None
        if isinstance(self.ingestor, (list, tuple)):
            return merge_freshness([i.freshness() for i in self.ingestor])
        return self.ingestor.freshness()

    #: the ONLY names ``query()`` dispatches — the web interface's raw
    #: query surface must not reach arbitrary attributes (``now``,
    #: private helpers, the index objects themselves)
    QUERY_METHODS = frozenset({
        "stat", "find_by_name", "find_by_glob", "world_writable",
        "not_accessed_since", "large_cold_files", "duplicate_candidates",
        "owned_by_deleted_users", "past_retention", "directories_over",
        "storage_by_project", "quota_pressure", "most_small_files",
        "per_user_usage", "dir_size_percentile", "top_storage_users",
        "du", "subtree_summary", "hot_directories", "find",
    })

    def query(self, name: str, *args, **kw) -> Dict:
        """Run a named query and stamp the result with the freshness
        watermark it was read at — the shape the paper's web interface
        returns ({"result": ..., "freshness": {...}}). ``name`` must be
        in ``QUERY_METHODS`` (raw web-interface input must not dispatch
        to arbitrary engine attributes)."""
        if name not in self.QUERY_METHODS:
            raise ValueError(
                f"unknown query {name!r}; expected one of "
                f"{sorted(self.QUERY_METHODS)}")
        fn = getattr(self, name)
        return {"result": fn(*args, **kw), "freshness": self.freshness()}

    # -- the discovery-index planner (DESIGN.md §11.3) ------------------------
    #
    # Each selective primary-index query below first asks the planner
    # for an accelerated answer: candidate prefilter through the
    # discovery index's sorted runs / trigram postings, exact verify
    # against the primary arenas. The planner routes to the index ONLY
    # when every shard's discovery index is attached and fresh;
    # otherwise it transparently falls back to the scan path. Either
    # route returns byte-identical results (tests/test_discovery.py
    # pins this property across corpora, delta fill, staleness, and
    # shard counts). ``last_plan`` records the routing decision.

    @property
    def last_plan(self) -> Optional[Dict]:
        """Routing record of THIS THREAD's most recent plannable query:
        {"query", "route": "discovery"|"scan", "reason", "candidates"}.
        Thread-local — it used to be a shared attribute, so two
        interleaved planner queries read each other's plans
        (tests/test_query_service.py pins the regression)."""
        return getattr(self._plan_tls, "plan", None)

    @last_plan.setter
    def last_plan(self, value: Optional[Dict]) -> None:
        self._plan_tls.plan = value

    def _discovery_route(self):
        """(shard discovery list, reason) — list is None on fallback."""
        ds = disc.discovery_shards(self.primary)
        if ds is None:
            self._c_fallback.labels("unattached").inc()
            return None, "no discovery index attached"
        if not all(d.fresh for d in ds):
            self._c_fallback.labels("stale").inc()
            return None, "discovery index stale (pending rebuild)"
        return ds, "fresh"

    def _plan(self, qname: str, shard_query) -> Optional[np.ndarray]:
        """Common planner tail: route check, per-shard fan-out +
        shard-order merge (== the scan's shard-major row order), and
        the ``last_plan`` record. None -> caller scans."""
        ds, reason = self._discovery_route()
        if ds is None:
            self.last_plan = {"query": qname, "route": "scan",
                              "reason": reason}
            return None
        parts = [shard_query(d) for d in ds]
        self.last_plan = {
            "query": qname, "route": "discovery", "reason": reason,
            "candidates": sum(d.stats.get("last_candidates", 0)
                              for d in ds)}
        return parts[0] if len(parts) == 1 else np.concatenate(parts)

    def _plan_select(self, qname: str,
                     preds: Sequence[Tuple[str, str, object]]
                     ) -> Optional[np.ndarray]:
        """Accelerated predicate query, or None -> caller scans. Route
        order: discovery index (attached + fresh) -> fused predicate
        kernel (enabled + program expressible) -> numpy scan."""
        got = self._plan(qname, lambda d: d.select(preds))
        if got is not None:
            return got
        return self._kernel_select(qname, preds)

    # -- the fused predicate-kernel route (DESIGN.md §13) ---------------------

    def _index_shards(self) -> List:
        """The physical shards a scan walks, in scan (shard-major)
        order — PrimaryIndex / IndexSnapshot duck-typed alike."""
        shards = getattr(self.primary, "shards", None)
        return list(shards) if shards is not None else [self.primary]

    def _shard_arena(self, si: int, sh, n: int):
        """The (shard, epoch) device arena slab, cached per shard
        position; a mutation-epoch or row-count change rebuilds."""
        key = (int(sh.mutation_epoch), n)
        hit = self._arena_cache.get(si)
        if hit is not None and hit[0] == key:
            return hit[1]
        with self._span_pack:              # host pack and H2D copy
            arena = pk_ops.pack_arena(sh.columns, sh.alive, n)
        self._arena_cache[si] = (key, arena)
        return arena

    def _shard_names(self, si: int, sh, arena):
        """The (shard, epoch) basename column for the find's prefilter,
        padded to the arena's rows and cached like it."""
        key = (int(sh.mutation_epoch), arena.n)
        hit = self._names_cache.get(si)
        if hit is not None and hit[0] == key:
            return hit[1]
        with self._span_pack:              # host pack and H2D copy
            col = pk_names.pack_names(sh.paths, arena.n, arena.n_pad)
        self._names_cache[si] = (key, col)
        return col

    def _kernel_pass(self, batch: Sequence[Tuple[str, List, dict, object]]
                     ) -> Tuple[List[np.ndarray], Dict[str, int]]:
        """One fused kernel pass per shard over the stacked programs of
        ``batch`` (``(query name, predicate list, compiled program, name
        literals)`` each), then per program the bitmap's candidate
        slots, exact-verified against the shard's arenas. An entry whose
        literals are not None (``names.name_literals``'s operand) also
        takes the basename prefilter, ANDed into its bitmap on the
        device before the one readback. Returns each program's verified
        paths (shard-major, i.e. scan order) and the summed counts:
        ``candidates`` (the predicate bitmaps alone), and for the named
        entries ``name_device`` (rows left after the AND) and
        ``name_keep`` (keep-flagged rows of the shards)."""
        progs = pk_ref.stack_programs([p for _, _, p, _ in batch])
        named = [(j, lits) for j, (_, _, _, lits) in enumerate(batch)
                 if lits is not None]
        parts: List[List[np.ndarray]] = [[] for _ in batch]
        stats = {"candidates": 0, "name_device": 0, "name_keep": 0}
        for si, sh in enumerate(self._index_shards()):
            n = _shard_rows(sh)
            arena = self._shard_arena(si, sh, n)
            col = self._shard_names(si, sh, arena) if named else None
            # program H2D, kernel, name prefilter, readback
            with self._span_device:
                dev = pk_ops.predeval_device(arena, progs)
                kept = []
                for j, lits in named:
                    dev, k = pk_names.and_row(dev, j, col, lits)
                    kept.append(k)
                words, kept = jax.device_get((dev, kept))
            kernel = {j: int(k) for (j, _), k in zip(named, kept)}
            if named:
                stats["name_keep"] += col.n_keep
            for j, (qname, preds, _, _) in enumerate(batch):
                with self._span_unpack:
                    cand = pk_ops.bitmap_slots(words, j, n)
                with self._span_verify:
                    got = disc.verify_select(sh.alive, sh.columns,
                                             sh.paths, cand, preds)
                k = kernel.get(j, len(cand))
                self._c_candidates.labels(qname, "kernel").inc(k)
                if j in kernel:
                    self._c_candidates.labels(
                        qname, "name_device").inc(len(cand))
                    stats["name_device"] += len(cand)
                self._c_candidates.labels(qname, "verified").inc(len(got))
                stats["candidates"] += k
                parts[j].append(got)
        return [p[0] if len(p) == 1 else np.concatenate(p)
                for p in parts], stats

    def _kernel_select(self, qname: str,
                       preds: Sequence[Tuple[str, str, object]]
                       ) -> Optional[np.ndarray]:
        """One fused kernel pass per shard: compile the predicate list
        into a program, evaluate the packed match bitmap over the arena
        epoch, then exact-verify the candidate slots against the
        primary arenas — the discovery index's superset discipline, so
        the result is byte-identical to the scan path in scan order.
        None -> inexpressible program or kernels disabled (caller
        scans). Inside ``find`` this thread's basename literal operand
        (``names.name_literals``) rides along and is prefiltered on the
        device with the program."""
        if not self.use_kernels:
            return None
        prog = pk_ref.compile_program(preds)
        if prog is None:
            plan = self.last_plan or {}
            self.last_plan = dict(plan, reason=(
                f"{plan.get('reason', '')}; program inexpressible"))
            return None
        why = (self.last_plan or {}).get("reason", "")
        name_lits = getattr(self._plan_tls, "name_lits", None)
        (got,), stats = self._kernel_pass([(qname, preds, prog, name_lits)])
        plan = {"query": qname, "route": "kernel",
                "reason": f"fused kernel ({why})",
                "candidates": stats["candidates"]}
        if name_lits is not None:
            plan.update(name_prefilter="device",
                        name_device=stats["name_device"],
                        name_keep=stats["name_keep"])
        self.last_plan = plan
        return got

    def _scan_select(self, preds: Sequence[Tuple[str, str, object]]
                     ) -> np.ndarray:
        """The ground-truth scan: exact predicates over the ``live()``
        view (what every accelerated route must byte-match)."""
        live = self.primary.live()
        m = np.ones(len(live["path"]), dtype=bool)
        for col, op, arg in preds:
            m &= disc.eval_pred(live[col], op, arg)
        return live["path"][m]

    def _pred_query(self, qname: str,
                    preds: Sequence[Tuple[str, str, object]]
                    ) -> np.ndarray:
        """Full route cascade for an already-built predicate list (the
        Table-I methods and the serving tier's time-pinned execution
        both land here)."""
        t0 = self.telemetry.clock()
        got = self._plan_select(qname, preds)
        if got is None:
            got = self._scan_select(preds)
        plan = self.last_plan or {}
        route = (plan.get("route", "scan")
                 if plan.get("query") == qname else "scan")
        self._h_route_s.labels(route).observe(self.telemetry.clock() - t0)
        return got

    def select_many(self, specs: Sequence, now: Optional[float] = None
                    ) -> List:
        """Batched query execution (tentpole part c): every expressible
        predicate query in ``specs`` — each a ``(name, args, kw)``
        tuple — compiles into one stacked program batch, evaluated in
        ONE fused kernel pass per shard (one arena read amortized
        across the whole batch, K bitmaps out), then exact-verified per
        query. Non-predicate or inexpressible entries dispatch through
        their normal route. Results align with ``specs`` and are
        byte-identical to running each query alone; time-relative
        cutoffs all resolve against the single ``now`` (default: this
        engine's clock, read once), so a dashboard's queries agree on
        what time it is."""
        now = self.now if now is None else float(now)
        specs = [(name, tuple(args), dict(kw)) for name, args, kw in specs]
        for name, _, _ in specs:
            if name not in self.QUERY_METHODS:
                raise ValueError(
                    f"unknown query {name!r}; expected one of "
                    f"{sorted(self.QUERY_METHODS)}")
        results: List = [None] * len(specs)
        preds_by_i: Dict[int, List] = {}
        batch: List[Tuple[int, List, dict]] = []
        for i, (name, args, kw) in enumerate(specs):
            preds = pred_spec(name, args, kw, now)
            if preds is None:
                continue
            preds_by_i[i] = preds
            if self.use_kernels:
                prog = pk_ref.compile_program(preds)
                if prog is not None:
                    batch.append((i, preds, prog))
        batched = {i for i, _, _ in batch}
        if batch:
            got, stats = self._kernel_pass(
                [(specs[i][0], preds, prog, None)
                 for i, preds, prog in batch])
            for (i, _, _), res in zip(batch, got):
                results[i] = res
            self.last_plan = {"query": "select_many", "route": "kernel",
                              "batched": len(batch),
                              "fallback": len(specs) - len(batch),
                              "candidates": stats["candidates"]}
        for i, (name, args, kw) in enumerate(specs):
            if i in batched:
                continue
            if i in preds_by_i:
                # predicate query the kernel could not take (or kernels
                # disabled): same cascade, same pinned now
                results[i] = self._pred_query(name, preds_by_i[i])
            else:
                results[i] = getattr(self, name)(*args, **kw)
        return results

    def _plan_names(self, qname: str, literals: Sequence[str],
                    match) -> Optional[np.ndarray]:
        """Accelerated name query: trigram candidates from the
        literals guaranteed in any match, verified by ``match`` (the
        exact compiled matcher). None -> caller scans (no usable
        literal, or discovery unavailable/stale)."""
        codes = disc.literal_trigrams(literals)
        if not codes:
            self.last_plan = {"query": qname, "route": "scan",
                              "reason": "no literal >= 3 bytes in pattern"}
            return None
        return self._plan(qname, lambda d: d.name_select(codes, match))

    # -- individual-granularity queries (primary index) ----------------------

    def stat(self, path: str) -> Optional[Dict]:
        """Point lookup by exact subject: one slot-map probe — on a
        sharded primary this routes to the single owning shard, no
        scatter (DESIGN.md §8)."""
        return self.primary.lookup(path)

    def find_by_name(self, pattern: str) -> np.ndarray:
        """name LIKE "*pattern*" (regex-match raw mode). Planner: the
        literals guaranteed in any match prefilter through the trigram
        index; each candidate is verified with the real compiled regex,
        so results are byte-identical to the scan. Fallback (stale
        index / no >=3-byte literal): scan the path-only live view
        (``live_paths``) — no full-column materialization — with the
        regex compiled once and its bound ``search`` applied in a
        single comprehension pass."""
        search = re.compile(pattern).search
        got = self._plan_names("find_by_name", disc.regex_literals(pattern),
                               lambda p: search(p) is not None)
        if got is not None:
            return got
        paths = self.primary.live_paths()
        return paths[[i for i, p in enumerate(paths) if search(p)]]

    def find_by_glob(self, pattern: str) -> np.ndarray:
        """name LIKE a shell glob (the web interface's non-regex search
        box). Same planner/fallback split as ``find_by_name``, with
        ``fnmatch.fnmatchcase`` as the exact verifier."""
        got = self._plan_names(
            "find_by_glob", disc.glob_literals(pattern),
            lambda p: fnmatch.fnmatchcase(p, pattern))
        if got is not None:
            return got
        paths = self.primary.live_paths()
        return paths[[i for i, p in enumerate(paths)
                      if fnmatch.fnmatchcase(p, pattern)]]

    def find(self, name: str, size: float, newer: float) -> np.ndarray:
        """``find -name NAME -size SIZEc -newer T`` (GNU find; IO500's
        find phase): live records whose basename (the path after its
        last ``/``) matches the glob ``name`` under
        ``fnmatch.fnmatchcase``, whose stored ``size`` equals ``size``
        and whose stored ``mtime`` is strictly after ``newer``, in scan
        order. Both attributes compare on the float32 columns with the
        arguments rounded to float32 (DESIGN.md §13.5, §13.7): ``size``
        is the one-ulp open interval around ``f32(size)``, which holds
        exactly that value. The attribute part runs the route cascade
        (``_pred_query``: discovery -> kernel -> scan). On the kernel
        route the glob's literal runs prefilter the basenames on the
        device, ANDed into the program's bitmap (``names.py``: a superset
        of the matches); the exact glob then runs on the verified
        paths, so every route returns the same answer."""
        s = np.float32(size)
        if not np.isfinite(s):
            raise ValueError(f"find: size must be finite, got {size!r}")
        preds = [("size", "gt", float(np.nextafter(s, np.float32(-np.inf)))),
                 ("size", "lt", float(np.nextafter(s, np.float32(np.inf)))),
                 ("mtime", "gt", float(newer))]
        # the kernel route reads the literals from this thread's state,
        # so the cascade's signatures stay those of every predicate query
        self._plan_tls.name_lits = pk_names.name_literals(
            disc.glob_literals(name))
        try:
            paths = self._pred_query("find", preds)
        finally:
            self._plan_tls.name_lits = None
        plan = self.last_plan or {}
        route = plan.get("name_prefilter", "host")
        self._c_prefilter.labels(route).inc()
        with self._span_name:
            match = re.compile(fnmatch.translate(name)).match
            # match at the basename's offset: no slice per path
            got = paths[[i for i, p in enumerate(paths)
                         if match(p, p.rfind("/") + 1)]]
        self._c_candidates.labels("find", "matched").inc(len(got))
        self.last_plan = dict(plan, name_prefilter=route,
                              verified=len(paths), matched=len(got))
        return got

    def world_writable(self) -> np.ndarray:
        """Table I "world-writable files" (security audit): mode & 0o002.
        Route cascade (``_pred_query``): discovery-index mode-run sweep
        -> fused predicate kernel -> live() scan, all byte-identical."""
        return self._pred_query("world_writable",
                                [("mode", "mask", 0o002)])

    def not_accessed_since(self, seconds: float) -> np.ndarray:
        """Table I "not accessed in N months" (cold-data candidates)."""
        return self._pred_query("not_accessed_since",
                                [("atime", "lt", self.now - seconds)])

    def large_cold_files(self, min_size: float, idle_seconds: float) -> np.ndarray:
        """Table I "large files with low access" (tiering candidates).

        ``min_size`` compares against the float32 ``size`` arena — see
        the storage-dtype rounding contract (DESIGN.md §13.5): above
        2^24 bytes the STORED size is the float32 rounding of the true
        size, and the threshold itself is rounded to float32 before the
        compare (numpy weak-scalar promotion). Every route — scan,
        discovery, kernel — applies the same rounding; the directed
        boundary test in tests/test_query_fixes.py pins agreement."""
        return self._pred_query("large_cold_files",
                                [("size", "gt", min_size),
                                 ("atime", "lt", self.now - idle_seconds)])

    def duplicate_candidates(self) -> Dict[int, np.ndarray]:
        """GROUP BY checksum HAVING count > 1 (``path_hash`` as the
        stand-in checksum column), keyed by the hash value. Same-size
        files with different hashes are NOT candidates — grouping by
        ``size`` here was a bug that flooded the report on any corpus
        with repeated sizes.

        Grouping is one stable argsort + boundary scan: the previous
        implementation rescanned the full inverse array once per
        duplicated group (``inv == ui`` in a Python loop — O(groups *
        n), quadratic on dedup-heavy corpora; the regression test in
        tests/test_query_fixes.py bounds the fixed cost). Stable sort
        keeps live-row order within each group, so the output is
        identical: keys ascending, paths in scan order."""
        live = self.primary.live()
        hashes = live["path_hash"].astype(np.int64)
        order = np.argsort(hashes, kind="stable")
        h = hashes[order]
        starts = np.flatnonzero(np.r_[True, h[1:] != h[:-1]])
        ends = np.r_[starts[1:], len(h)]
        paths = live["path"]
        out = {}
        for gi in np.flatnonzero(ends - starts > 1):
            s = starts[gi]
            out[int(h[s])] = paths[order[s:ends[gi]]]
        return out

    def owned_by_deleted_users(self, active_uids: Sequence[int]) -> np.ndarray:
        """Table I "files owned by deleted users" (orphan sweep)."""
        return self._pred_query("owned_by_deleted_users",
                                [("uid", "notin", list(active_uids))])

    def past_retention(self, retention_seconds: float) -> np.ndarray:
        """Table I "past retention policy" (purge candidates)."""
        return self._pred_query(
            "past_retention",
            [("mtime", "lt", self.now - retention_seconds)])

    # -- aggregate-granularity queries (aggregate index) ----------------------

    def directories_over(self, n_files: float) -> List[str]:
        """Table I "directories with > N entries". Aggregate-index read:
        per-principal records are whole (never half-written), but may
        trail the primary index by one apply (DESIGN.md §6.3)."""
        return [p for p, c in self.aggregate.records.items()
                if p.startswith("dir:") and c["file_count"] > n_files]

    def storage_by_project(self) -> Dict[str, float]:
        """SUM(size) GROUP BY project — projects are groups here."""
        return {p: c["size"]["total"] for p, c in self.aggregate.records.items()
                if p.startswith("group:")}

    def quota_pressure(self, quotas: Dict[str, float], thresh: float = 0.9
                       ) -> List[Tuple[str, float]]:
        """Table I "principals near quota": total size / quota > thresh."""
        out = []
        for p, c in self.aggregate.records.items():
            q = quotas.get(p)
            if q and c["size"]["total"] / q > thresh:
                out.append((p, c["size"]["total"] / q))
        return out

    def most_small_files(self, k: int = 10) -> List[Tuple[str, float]]:
        """COUNT(file_size < 1MB) DESC per user — estimated from each
        user's size-sketch CDF at 1 MB (sketch-powered semantic query)."""
        live = self.primary.live()
        # exact path for validation:
        users, counts = np.unique(live["uid"][live["size"] < 1e6],
                                  return_counts=True)
        order = np.argsort(-counts)
        return [(f"user:{int(users[i])}", float(counts[i]))
                for i in order[:k]]

    def per_user_usage(self) -> Dict[str, Tuple[float, float]]:
        """SUM(size), COUNT(*) GROUP BY uid."""
        return {p: (c["size"]["total"], c["file_count"])
                for p, c in self.aggregate.records.items()
                if p.startswith("user:")}

    def dir_size_percentile(self, q: str = "p99") -> Dict[str, float]:
        """PERCENTILE(size, q) for directory principals."""
        return {p: c["size"][q] for p, c in self.aggregate.records.items()
                if p.startswith("dir:")}

    def top_storage_users(self, k: int = 10) -> List[Tuple[str, float]]:
        """Table I "top storage consumers" (admin dashboard staple)."""
        items = [(p, c["size"]["total"])
                 for p, c in self.aggregate.records.items()
                 if p.startswith("user:")]
        items.sort(key=lambda x: -x[1])
        return items[:k]

    # -- subtree-rollup queries (DESIGN.md §14) -------------------------------
    #
    # du-on-any-directory and friends route through the attached
    # HierarchyIndex when its rollups are exact (bounded lazy
    # propagation, O(dirty + answer)); otherwise they fall back to a
    # brute-force scan over ``live()``. Both routes share the
    # quantization contract (hierarchy.size_bytes_i64 / atime_bucket),
    # so results are byte-identical — tests/test_rollup.py pins it.

    def _hier_route(self, name: str):
        """(hierarchy | None, plan) — hierarchy is None on fallback."""
        h = self.hierarchy
        if h is None:
            return None, {"query": name, "route": "scan",
                          "reason": "no hierarchy index attached"}
        if not h.exact:
            return None, {"query": name, "route": "scan",
                          "reason": "rollups invalidated (bulk load or "
                                    "compaction without reseed)"}
        return h, {"query": name, "route": "rollup", "reason": "exact"}

    def du(self, path: str, depth: int = 0) -> Dict:
        """The paper's flagship admin query at last: aggregate summary
        statistics for ANY directory — live file count, total bytes
        (int64-quantized), max mtime — plus per-subdirectory rows down
        to ``depth`` levels below ``path`` (0 = totals only)."""
        h, plan = self._hier_route("du")
        self.last_plan = plan
        if h is not None:
            return h.du(path, depth=depth)
        return hier.du_scan(self.primary.live(), path, depth=depth)

    def subtree_summary(self, path: str) -> Dict:
        """``du`` totals plus the coarse atime histogram (bucket counts
        and bytes over hierarchy.ATIME_EDGES_S, anchored at REF_TIME)
        and the number of distinct directories holding live files —
        the retention/tiering view a policy rule evaluates against."""
        h, plan = self._hier_route("subtree_summary")
        self.last_plan = plan
        if h is not None:
            return h.subtree_summary(path)
        return hier.subtree_summary_scan(self.primary.live(), path)

    def hot_directories(self, k: int = 10, buckets: int = 2) -> List[Dict]:
        """Top-k directories by own-grain (non-recursive) bytes in the
        ``buckets`` most-recent atime buckets — "where is the hot data"
        at directory granularity, REF_TIME-anchored so the ranking is
        a property of the corpus, not of when you asked."""
        h, plan = self._hier_route("hot_directories")
        self.last_plan = plan
        if h is not None:
            return h.hot_directories(k=k, buckets=buckets)
        return hier.hot_directories_scan(self.primary.live(),
                                         k=k, buckets=buckets)

    # -- the full Table I suite, each query timed on the host clock -----------

    def run_table1_suite(self) -> Dict[str, float]:
        timings = {}

        def timed(name, fn, *a):
            t0 = time.perf_counter()
            fn(*a)
            timings[name] = time.perf_counter() - t0

        timed("name_like", self.find_by_name, r"f1\d\d$")
        timed("world_writable", self.world_writable)
        timed("not_accessed_12m", self.not_accessed_since, 365 * 86400)
        timed("large_low_access", self.large_cold_files, 100e9, 180 * 86400)
        timed("duplicates", self.duplicate_candidates)
        timed("dirs_over_100k", self.directories_over, 100_000)
        timed("storage_by_project", self.storage_by_project)
        timed("quota_pressure", self.quota_pressure,
              {p: 1e12 for p in self.aggregate.records}, 0.9)
        timed("deleted_users", self.owned_by_deleted_users, list(range(16)))
        timed("past_retention", self.past_retention, 2 * 365 * 86400)
        timed("most_small_files", self.most_small_files)
        timed("per_user_usage", self.per_user_usage)
        timed("dir_p99", self.dir_size_percentile)
        return timings
