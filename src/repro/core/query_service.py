"""Concurrent query service over MVCC snapshot reads (DESIGN.md §12).

The paper's headline posture is interactive analytics *while* ingestion
keeps running; until this module every query walked the live arenas in
the ingest thread, so readers and writers serialized. ``QueryService``
is the serving tier on top of the index snapshots (core/mvcc.py):

- **admission**: up to ``max_readers`` queries run concurrently, all
  served from ONE pooled pinned snapshot per data version (re-pinned
  only when the version advances) — numpy scans release the GIL, so
  readers overlap each other and the writer for real, and the pin cost
  amortizes across every read at that version;
- **watermark tokens**: every snapshot carries the service's *data
  version* — the ingest watermark as of the last MUTATING apply. The
  ingestor's ``on_apply`` hook advances it (under the primary write
  lock, so tokens and pinned state move atomically); no-op applies
  (a batch coalescing to nothing) advance the raw watermark but NOT the
  data version, because the readable state did not change;
- **result cache**: keyed by (query, params, data version) and
  invalidated by data-version advance — never TTL. A hit is exact by
  construction: same query, same params, same readable state;
- **cursors**: ``query_page`` keeps its snapshot pinned between pages
  and embeds the snapshot's watermark token in the cursor, so pages
  never skip or duplicate rows no matter how far ingest advances
  between page fetches. Cursors drain-close automatically (or via
  ``close_cursor``).

Out-of-band writers (direct index mutations that bypass the ingestor —
maintenance scripts, tests) are caught at snapshot time by comparing
the mutation-epoch sum; the service then invalidates the cache and
bumps its data version, so correctness never depends on every writer
being hook-registered — only cache retention does.

Lock order is primary write lock -> service lock everywhere (the
ingestor's hook fires under the primary lock; ``snapshot()`` takes the
primary lock first for the same reason). Query execution itself holds
neither lock.
"""
from __future__ import annotations

import contextlib
import copy
import itertools
import threading
from collections import OrderedDict
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from repro.core.index import AggregateIndex
from repro.core.query import (HIER_QUERIES, TIME_RELATIVE, QueryEngine,
                              merge_freshness, pred_spec)
from repro.core.telemetry import resolve as _resolve_tel


def _canon(obj) -> Any:
    """Hashable canonical form of query params (cache-key component):
    dicts/sets order-insensitively, arrays/lists by value. Falls back
    to ``repr`` for exotic unhashables — at worst a missed cache hit,
    never a wrong one (the key still distinguishes distinct reprs)."""
    if isinstance(obj, dict):
        return ("d", tuple(sorted((k, _canon(v)) for k, v in obj.items())))
    if isinstance(obj, (list, tuple)):
        return ("l", tuple(_canon(x) for x in obj))
    if isinstance(obj, (set, frozenset)):
        return ("s", tuple(sorted(map(repr, obj))))
    if isinstance(obj, np.ndarray):
        return ("a", str(obj.dtype), obj.shape, obj.tobytes())
    try:
        hash(obj)
        return obj
    except TypeError:
        return ("r", repr(obj))


def mutation_epochs(primary) -> int:
    """Layout-wide mutation-epoch sum (monolith or sharded): the ground
    truth that readable state changed, whatever path changed it."""
    shards = getattr(primary, "shards", None)
    if shards is None:
        return int(primary.mutation_epoch)
    return int(sum(sh.mutation_epoch for sh in shards))


class ResultCache:
    """LRU result cache keyed by (query, canonical params, data
    version). Invalidation is event-driven — ``invalidate()`` on every
    mutating watermark advance — so entries are never served stale and
    never expire while the data stands still (no TTL)."""

    _MISS = object()

    def __init__(self, capacity: int = 256):
        self.capacity = int(capacity)
        self._d: "OrderedDict[Tuple, Any]" = OrderedDict()
        self.stats = {"hits": 0, "misses": 0, "invalidations": 0,
                      "entries_dropped": 0, "evicted": 0}

    def get(self, key: Tuple) -> Any:
        got = self._d.get(key, self._MISS)
        if got is self._MISS:
            self.stats["misses"] += 1
            return self._MISS
        self._d.move_to_end(key)
        self.stats["hits"] += 1
        return got

    def put(self, key: Tuple, value: Any) -> None:
        self._d[key] = value
        self._d.move_to_end(key)
        while len(self._d) > self.capacity:
            self._d.popitem(last=False)
            self.stats["evicted"] += 1

    def invalidate(self) -> None:
        """Drop everything: the data version advanced, so every cached
        result is keyed at a state no new snapshot will pin."""
        self.stats["invalidations"] += 1
        self.stats["entries_dropped"] += len(self._d)
        self._d.clear()

    def hit_rate(self) -> float:
        t = self.stats["hits"] + self.stats["misses"]
        return self.stats["hits"] / t if t else 0.0

    def __len__(self) -> int:
        return len(self._d)


class _PinnedFreshness:
    """Duck-typed stand-in for an ingestor whose ``freshness()`` is the
    mark captured at snapshot-pin time: a snapshot's results must carry
    the watermark of the state they READ, not whatever the live
    ingestor has advanced to by response time."""

    def __init__(self, mark: Optional[Dict]):
        self._mark = mark

    def freshness(self) -> Optional[Dict]:
        return self._mark


class ServiceSnapshot:
    """One pinned read context: the MVCC index view, the watermark
    token it pinned, and a ``QueryEngine`` bound to the frozen state
    (pinned aggregate records, pinned freshness mark). Close it — it is
    a context manager — to release the pin.

    The rollup queries (query.HIER_QUERIES) read the LIVE hierarchy
    index against the pinned primary view — the rollup tree is not
    MVCC-versioned. That is per-query bounded-FORWARD consistency
    (same as discovery acceleration): the tree reflects the primary
    state at or ahead of the pinned watermark, never behind it, and
    the service keys their cache entries on the hierarchy's apply
    epoch so an advance can never serve a pre-advance answer."""

    def __init__(self, service: "QueryService", view, aggregate,
                 watermark: int):
        self._service = service
        self.view = view
        self.watermark = int(watermark)
        self.engine = QueryEngine(
            view, aggregate, now=service._now,
            ingestor=_PinnedFreshness(view.freshness_mark),
            use_kernels=service._use_kernels,
            hierarchy=service._hierarchy(),
            telemetry=service.telemetry)
        self._closed = False

    @property
    def closed(self) -> bool:
        return self._closed

    def query(self, name: str, *args, **kw) -> Dict:
        """Uncached convenience passthrough (``QueryEngine.query``
        semantics against the pinned state)."""
        return self.engine.query(name, *args, **kw)

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        self.view.close()
        self._service._snapshot_closed(self.watermark)

    def __enter__(self) -> "ServiceSnapshot":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


class QueryService:
    """Concurrent reader tier over one primary/aggregate pair (see
    module docstring). ``ingestor`` (one, a list, or None) supplies the
    watermark and the ``on_apply`` invalidation hook; ``now`` is the
    query clock passed through to the engines; ``pin_aggregate``
    deep-copies aggregate records into each snapshot so aggregate
    queries are as frozen as primary ones (disable for cheap pins when
    no writer touches the aggregate)."""

    def __init__(self, primary, aggregate: Optional[AggregateIndex] = None,
                 ingestor=None, now=None, max_readers: int = 16,
                 cache_capacity: int = 256, pin_aggregate: bool = True,
                 now_bucket_s: float = 1.0, use_kernels: bool = True,
                 telemetry=None):
        """``now_bucket_s``: freshness bucket for TIME-RELATIVE query
        caching (``not_accessed_since`` / ``large_cold_files`` /
        ``past_retention``). Their cutoffs derive from the wall clock,
        so watermark keying alone would serve a frozen cutoff forever
        at an idle index; instead the resolved clock, quantized to this
        bucket, joins their cache keys — hits still coalesce within a
        bucket, and answers can never be more than one bucket stale in
        wall-clock terms. <= 0 keys on the raw clock (every call
        misses). ``use_kernels`` passes through to the snapshot
        engines (core/query.py)."""
        self.primary = primary
        self.aggregate = aggregate if aggregate is not None \
            else AggregateIndex()
        self.ingestor = ingestor
        self._now = now
        self.now_bucket_s = float(now_bucket_s)
        self._use_kernels = use_kernels
        self._pin_aggregate = bool(pin_aggregate)
        self.cache = ResultCache(cache_capacity)
        self._sem = threading.BoundedSemaphore(int(max_readers))
        self.max_readers = int(max_readers)
        self._lock = threading.Lock()
        mark = self._freshness_mark()
        self._data_version = int(mark["applied_seq"]) if mark else 0
        self._epoch_sum = mutation_epochs(primary)
        self._open_tokens: Dict[int, int] = {}   # token -> open snapshots
        #: the snapshot pool: ONE pinned snapshot shared by every query
        #: at the current data version ({"snap", "users", "retired"}).
        #: A cache hit or same-version read then costs a refcount bump
        #: instead of a fresh pin — re-pinning only on version advance.
        self._pool: Optional[Dict] = None
        self._cursors: Dict[int, Dict] = {}
        self._cursor_ids = itertools.count(1)
        #: single-flight table: cache key -> Event, one per key being
        #: computed right now, so N readers missing the same key at the
        #: same watermark do ONE scan between them
        self._inflight: Dict[Tuple, threading.Event] = {}
        self.stats = {"queries": 0, "pages": 0, "snapshots": 0,
                      "cursors_opened": 0, "cursors_closed": 0,
                      "coalesced": 0, "batches": 0}
        self.telemetry = _resolve_tel(telemetry)
        self._c_hits = self.telemetry.counter(
            "service_cache_hits_total", "result-cache hits")
        self._c_misses = self.telemetry.counter(
            "service_cache_misses_total", "result-cache misses (computed)")
        self._c_coalesced = self.telemetry.counter(
            "service_coalesced_total",
            "readers that waited on another reader's identical miss")
        self._g_pins = self.telemetry.gauge(
            "service_snapshot_pins", "open caller-held snapshot pins")
        self._h_query_s = self.telemetry.histogram(
            "service_query_seconds",
            "end-to-end query() latency by query name",
            labels=("query",))
        for ing in self._ingestors():
            hooks = getattr(ing, "on_apply", None)
            if hooks is not None:
                hooks.append(self._on_apply)

    # -- watermark bookkeeping ------------------------------------------------

    def _ingestors(self) -> List:
        if self.ingestor is None:
            return []
        if isinstance(self.ingestor, (list, tuple)):
            return list(self.ingestor)
        return [self.ingestor]

    def _hierarchy(self):
        """The live HierarchyIndex serving rollup queries, or None —
        ``_PinnedFreshness`` stand-ins carry no hierarchy, so snapshot
        engines must be handed the real one explicitly. Multi-ingestor
        deployments get None (each partition's tree covers only its
        shard's namespace slice; merging is future work) — the engines
        then use the byte-identical scan fallback."""
        ings = self._ingestors()
        if len(ings) == 1:
            return getattr(ings[0], "hierarchy", None)
        return None

    def _freshness_mark(self) -> Optional[Dict]:
        ings = self._ingestors()
        if not ings:
            return None
        if len(ings) == 1:
            return ings[0].freshness()
        return merge_freshness([i.freshness() for i in ings])

    def _on_apply(self, seq: int, mutated: bool) -> None:
        """Ingestor hook, called under the primary write lock. A
        mutating apply advances the data version and drops the cache
        (every entry is keyed at an older version); a no-op apply
        leaves both alone — its cached results are still exact, which
        is the whole point of keying on the MUTATING watermark."""
        if not mutated:
            return
        with self._lock:
            self.cache.invalidate()
            # strictly monotone even if a repair replays an old seq
            self._data_version = max(int(seq), self._data_version + 1)
            self._epoch_sum = mutation_epochs(self.primary)
            to_close = self._retire_pool_locked()
        if to_close is not None:
            to_close["snap"].close()

    def _refresh_version_locked(self) -> None:
        """Out-of-band writer detection (called under primary + service
        locks at snapshot time): if the mutation-epoch sum moved without
        an ``on_apply``, readable state changed behind the service's
        back — invalidate and advance, so stale cache entries cannot be
        served against the new state."""
        es = mutation_epochs(self.primary)
        if es == self._epoch_sum:
            return
        self.cache.invalidate()
        self._epoch_sum = es
        self._data_version += 1
        mark = self._freshness_mark()
        if mark:
            self._data_version = max(self._data_version,
                                     int(mark["applied_seq"]))

    @property
    def data_version(self) -> int:
        """The current watermark token (last MUTATING apply)."""
        with self._lock:
            return self._data_version

    # -- snapshots ------------------------------------------------------------

    def snapshot(self) -> ServiceSnapshot:
        """Pin one read context at the current data version. The
        primary write lock is taken FIRST (lock order: primary ->
        service), so the token, the freshness mark, the index pin, and
        the aggregate copy are all of the same instant — no apply can
        land between them."""
        wl = getattr(self.primary, "write_lock", None)
        ctx = wl() if wl is not None else contextlib.nullcontext()
        with ctx:
            with self._lock:
                self._refresh_version_locked()
                token = self._data_version
                mark = self._freshness_mark()
                view = self.primary.snapshot(freshness=mark)
                agg = (AggregateIndex(
                    records=copy.deepcopy(self.aggregate.records))
                    if self._pin_aggregate else self.aggregate)
                self._open_tokens[token] = \
                    self._open_tokens.get(token, 0) + 1
                self.stats["snapshots"] += 1
                self._g_pins.set(sum(self._open_tokens.values()))
        return ServiceSnapshot(self, view, agg, token)

    def _snapshot_closed(self, token: int) -> None:
        with self._lock:
            left = self._open_tokens.get(token, 0) - 1
            if left > 0:
                self._open_tokens[token] = left
            else:
                self._open_tokens.pop(token, None)
            self._g_pins.set(sum(self._open_tokens.values()))

    # -- the snapshot pool ----------------------------------------------------

    def _retire_pool_locked(self) -> Optional[Dict]:
        """Detach the pool entry (caller holds the service lock) and
        return it IF the caller must close it — closing takes the
        primary lock, so it happens after the service lock is released
        (lock order). With users in flight, the last ``_release_pooled``
        closes instead."""
        pool, self._pool = self._pool, None
        if pool is None:
            return None
        pool["retired"] = True
        return pool if pool["users"] == 0 else None

    def _acquire_pooled(self) -> Dict:
        """A pooled read context at the current data version. Fast path:
        the pool is current (same token, same mutation-epoch sum) — bump
        its refcount, no pin, no primary lock. Slow path: pin a fresh
        snapshot through ``snapshot()`` (full lock discipline) and
        install it as the new pool. The epoch probe reads shard counters
        without the primary lock — a stale read only mis-picks WHICH
        consistent snapshot serves, never serves inconsistent state."""
        with self._lock:
            pool = self._pool
            if pool is not None and not pool["retired"] \
                    and pool["snap"].watermark == self._data_version \
                    and mutation_epochs(self.primary) == self._epoch_sum:
                pool["users"] += 1
                return pool
        snap = self.snapshot()
        entry = {"snap": snap, "users": 1, "retired": False}
        with self._lock:
            to_close = self._retire_pool_locked()
            self._pool = entry
        if to_close is not None:
            to_close["snap"].close()
        return entry

    def _release_pooled(self, entry: Dict) -> None:
        with self._lock:
            entry["users"] -= 1
            close = entry["retired"] and entry["users"] == 0
        if close:
            entry["snap"].close()

    def close(self) -> None:
        """Release the service's internal snapshot pool so all arena
        pins return to baseline (idempotent; the service stays usable —
        the next query re-pins). Caller-held snapshots and open cursors
        remain the caller's to close."""
        with self._lock:
            to_close = self._retire_pool_locked()
        if to_close is not None:
            to_close["snap"].close()

    def detach(self) -> None:
        """Full teardown: unregister this service's ``on_apply`` hooks
        from every attached ingestor and release the snapshot pool —
        the inverse of ``__init__``. A decommissioned serving tier (a
        read replica being torn down, core/replication.py) must not
        keep receiving invalidation callbacks from an ingestor that
        outlives it. Idempotent; the service remains queryable but no
        longer tracks ingest (callers should drop it)."""
        for ing in self._ingestors():
            hooks = getattr(ing, "on_apply", None)
            if hooks is not None and self._on_apply in hooks:
                hooks.remove(self._on_apply)
        self.close()

    # -- queries --------------------------------------------------------------

    def _cache_key(self, name: str, args: Tuple, kw: Dict,
                   watermark: int, now: float) -> Tuple:
        """(query, canonical params, data version) — plus, for
        TIME-RELATIVE queries only, the resolved clock quantized to
        ``now_bucket_s``. Without the clock component an unchanged
        watermark would serve a cutoff computed from an earlier clock
        read indefinitely (tests/test_query_service.py pins the
        regression); with it, coalescing still works inside a bucket."""
        key = (name, _canon(args), _canon(kw), watermark)
        if name in TIME_RELATIVE:
            b = self.now_bucket_s
            key += (int(now // b) if b > 0 else now,)
        if name in HIER_QUERIES:
            # rollup queries read the LIVE hierarchy tree (see
            # ServiceSnapshot): its apply epoch joins the key so a
            # seed/invalidate/op batch that moves the tree without a
            # mutating primary apply cannot serve a pre-move answer
            h = self._hierarchy()
            key += ((int(h.apply_epoch), bool(h.exact))
                    if h is not None else None,)
        return key

    def _execute(self, snap: ServiceSnapshot, name: str, args: Tuple,
                 kw: Dict, now: float) -> Any:
        """Run one query on the snapshot engine. Time-relative queries
        resolve their cutoffs against the SAME ``now`` their cache key
        quantized (not a fresh clock read inside the method), so the
        key and the answer can never disagree about what time it is."""
        if name in TIME_RELATIVE:
            preds = pred_spec(name, args, kw, now)
            if preds is not None:
                return snap.engine._pred_query(name, preds)
        return getattr(snap.engine, name)(*args, **kw)

    def _run_cached(self, snap: ServiceSnapshot, name: str,
                    args: Tuple, kw: Dict) -> Tuple[Any, bool]:
        """Cache lookup with single-flight miss coalescing: the first
        reader to miss a key becomes its computer; every concurrent
        reader missing the SAME key at the same watermark waits on the
        computer's event and re-reads the cache, so an invalidation
        storm costs one scan per distinct query, not one per reader.
        Keys embed the watermark, so a late fill after an invalidation
        is dead weight the LRU evicts — never a wrong answer. If the
        computer raises, its waiters re-check, elect a new computer,
        and the loop converges."""
        if name not in QueryEngine.QUERY_METHODS:
            raise ValueError(
                f"unknown query {name!r}; expected one of "
                f"{sorted(QueryEngine.QUERY_METHODS)}")
        now = snap.engine.now
        key = self._cache_key(name, args, kw, snap.watermark, now)
        while True:
            with self._lock:
                got = self.cache.get(key)
                if got is not ResultCache._MISS:
                    self._c_hits.inc()
                    return got, True
                ev = self._inflight.get(key)
                if ev is None:
                    ev = threading.Event()
                    self._inflight[key] = ev
                    break               # this thread computes
                self.stats["coalesced"] += 1
                self._c_coalesced.inc()
            ev.wait()                   # computer fills the cache (or
            #                             fails; loop re-elects)
        try:
            result = self._execute(snap, name, args, kw, now)
            self._c_misses.inc()
            with self._lock:
                self.cache.put(key, result)
            return result, False
        finally:
            with self._lock:
                self._inflight.pop(key, None)
            ev.set()

    def query(self, name: str, *args, **kw) -> Dict:
        """Run one named query against the pooled pinned snapshot for
        the current data version, through the result cache. Returns the
        ``QueryEngine.query`` shape with the snapshot's watermark token
        and cache verdict added to the freshness mark."""
        tel = self.telemetry
        qt = tel.trace_query(name)
        t0 = tel.clock()
        with self._sem:
            entry = self._acquire_pooled()
            snap = entry["snap"]
            if qt is not None:
                qt.stage("acquire_snapshot")
            try:
                result, cached = self._run_cached(snap, name, args, kw)
            finally:
                self._release_pooled(entry)
        if qt is not None:
            qt.stage("execute")
        with self._lock:
            self.stats["queries"] += 1
        fresh = dict(snap.engine.freshness() or {})
        fresh["watermark"] = snap.watermark
        fresh["cached"] = cached
        self._h_query_s.labels(name).observe(tel.clock() - t0)
        if qt is not None:
            # the engine's thread-local plan is this thread's routing
            # record for the query just run (absent on cache hits and
            # non-plannable queries)
            plan = snap.engine.last_plan or {}
            if cached:
                route = "cache"
            elif plan.get("query") == name:
                route = plan.get("route", "direct")
            else:
                route = "direct"
            qt.finish(route=route, cached=cached,
                      candidates=plan.get("candidates"))
        return {"result": result, "freshness": fresh}

    def query_batch(self, requests) -> List[Dict]:
        """The dashboard entry point (DESIGN.md §13.4): run many named
        queries against ONE pooled snapshot and ONE resolved clock.
        Each request is ``(name, *args)`` or ``{"name", "args", "kw"}``;
        results align with ``requests``, each in the ``query()`` shape.

        Cache lookups come first (same keys as ``query()``, so batch
        and single-query traffic share entries); the misses then go
        through ``QueryEngine.select_many``, which fuses every
        expressible predicate query into one stacked kernel pass per
        shard — a 32-panel refresh costs a handful of kernel launches
        instead of 32 arena scans. Duplicate keys within a batch
        compute once. Batches skip the single-flight table (one fused
        pass IS the coalesced form; a concurrent ``query()`` for the
        same key at worst recomputes one entry)."""
        specs = []
        for r in requests:
            if isinstance(r, dict):
                specs.append((r["name"], tuple(r.get("args", ())),
                              dict(r.get("kw", {}))))
            else:
                name, *args = r
                specs.append((name, tuple(args), {}))
        for name, _, _ in specs:
            if name not in QueryEngine.QUERY_METHODS:
                raise ValueError(
                    f"unknown query {name!r}; expected one of "
                    f"{sorted(QueryEngine.QUERY_METHODS)}")
        out: List[Optional[Dict]] = [None] * len(specs)
        with self._sem:
            entry = self._acquire_pooled()
            snap = entry["snap"]
            try:
                now = snap.engine.now
                fresh_base = dict(snap.engine.freshness() or {})
                fresh_base["watermark"] = snap.watermark

                def wrap(result, cached):
                    fresh = dict(fresh_base, cached=cached)
                    return {"result": result, "freshness": fresh}

                miss_by_key: Dict[Tuple, List[int]] = {}
                keys = []
                with self._lock:
                    for i, (name, args, kw) in enumerate(specs):
                        key = self._cache_key(name, args, kw,
                                              snap.watermark, now)
                        keys.append(key)
                        got = self.cache.get(key)
                        if got is not ResultCache._MISS:
                            out[i] = wrap(got, True)
                        else:
                            miss_by_key.setdefault(key, []).append(i)
                if miss_by_key:
                    first = [idxs[0] for idxs in miss_by_key.values()]
                    results = snap.engine.select_many(
                        [specs[i] for i in first], now=now)
                    with self._lock:
                        for i, res in zip(first, results):
                            self.cache.put(keys[i], res)
                    for idxs, res in zip(miss_by_key.values(), results):
                        for j, i in enumerate(idxs):
                            out[i] = wrap(res, j > 0)
            finally:
                self._release_pooled(entry)
        with self._lock:
            self.stats["queries"] += len(specs)
            self.stats["batches"] += 1
        return out

    # -- pagination (ingest-stable cursors) -----------------------------------

    @staticmethod
    def _rows(result) -> Any:
        if isinstance(result, (np.ndarray, list, tuple)):
            return result
        raise TypeError(
            f"query result of type {type(result).__name__} is not "
            "paginable (row-sequence results only)")

    def query_page(self, name: Optional[str] = None, *args,
                   page_size: int = 100, cursor: Optional[Dict] = None,
                   **kw) -> Dict:
        """Paginated query. First call: ``query_page(name, *args,
        page_size=...)`` pins a snapshot, runs the query, returns the
        first page plus a cursor token ``{"cursor", "watermark",
        "offset"}``. Subsequent calls: ``query_page(cursor=token)``
        serve the next page FROM THE SAME pinned snapshot — the
        embedded watermark is checked against the pin, and because the
        result set was frozen at pin time, pages never skip or
        duplicate rows however far ingest advances in between. The
        snapshot auto-releases when the last page is served; abandon
        early via ``close_cursor``. One consumer per cursor."""
        with self._sem:
            if cursor is None:
                if name is None:
                    raise ValueError("query_page needs a name or a cursor")
                snap = self.snapshot()
                try:
                    result, _ = self._run_cached(snap, name, args, kw)
                    rows = self._rows(result)
                except BaseException:
                    snap.close()
                    raise
                cid = next(self._cursor_ids)
                entry = {"snap": snap, "rows": rows, "offset": 0,
                         "query": name}
                with self._lock:
                    self._cursors[cid] = entry
                    self.stats["cursors_opened"] += 1
            else:
                cid = int(cursor["cursor"])
                with self._lock:
                    entry = self._cursors.get(cid)
                if entry is None:
                    raise KeyError(f"cursor {cid} is closed or unknown")
                if int(cursor["watermark"]) != entry["snap"].watermark:
                    raise ValueError(
                        "cursor token watermark does not match its "
                        "pinned snapshot")
            rows = entry["rows"]
            off = entry["offset"]
            page = rows[off:off + int(page_size)]
            entry["offset"] = off + len(page)
            wm = entry["snap"].watermark
            done = entry["offset"] >= len(rows)
            with self._lock:
                self.stats["pages"] += 1
        tok = None
        if done:
            self.close_cursor(cid)
        else:
            tok = {"cursor": cid, "watermark": wm,
                   "offset": entry["offset"]}
        return {"rows": page, "cursor": tok, "watermark": wm,
                "total": len(rows), "done": done}

    def close_cursor(self, cursor) -> bool:
        """Release a cursor's pinned snapshot (idempotent; accepts the
        token dict or the raw id). True if the cursor was open."""
        cid = int(cursor["cursor"]) if isinstance(cursor, dict) \
            else int(cursor)
        with self._lock:
            entry = self._cursors.pop(cid, None)
            if entry is not None:
                self.stats["cursors_closed"] += 1
        if entry is None:
            return False
        entry["snap"].close()
        return True

    # -- freshness / monitoring ----------------------------------------------

    def freshness(self) -> Dict:
        """The ingest watermark (when an ingestor is attached) extended
        with the serving tier's marks: the served data version, open
        snapshots/cursors, how far the OLDEST open snapshot trails the
        current version (``snapshot_lag``), and cache accounting —
        what ``monitor.Monitor`` exports (DESIGN.md §12.4)."""
        base = self._freshness_mark() or {}
        with self._lock:
            toks = dict(self._open_tokens)
            if self._pool is not None:       # the service's own standing
                t = self._pool["snap"].watermark     # pin is not a reader
                if toks.get(t, 0) <= 1:
                    toks.pop(t, None)
                else:
                    toks[t] -= 1
            open_snaps = sum(toks.values())
            oldest = min(toks) if toks else None
            out = dict(base)
            out.update({
                "served_watermark": self._data_version,
                "open_snapshots": int(open_snaps),
                "open_cursors": len(self._cursors),
                "snapshot_lag": (self._data_version - oldest
                                 if oldest is not None else 0),
                "cache": {
                    "entries": len(self.cache),
                    "hits": self.cache.stats["hits"],
                    "misses": self.cache.stats["misses"],
                    "invalidations": self.cache.stats["invalidations"],
                    "hit_rate": self.cache.hit_rate(),
                },
            })
        return out
