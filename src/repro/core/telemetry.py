"""Process-wide telemetry: metrics registry, span tracing, exposition
(DESIGN.md §16).

The paper's operational posture — "tunable options for balancing
consistency, latency, and metadata freshness" — needs a surface that
answers *why is this query slow* and *how stale is what users see*
without re-running a benchmark. Three pieces, one handle:

- **metrics registry**: counters, gauges, and fixed-bucket histograms
  with labeled families (per-shard, per-route, per-replica). Scalar
  updates are plain attribute arithmetic (GIL-atomic best-effort: a
  racing ``+=`` can drop a count, never corrupt state — the same
  discipline the index's stats dicts already rely on); the registry
  lock is taken only on family creation. Histogram bucket state is
  numpy (``int64`` count vectors); scalar ``observe`` routes through
  ``bisect`` (C-implemented, ~100 ns), batched ``observe_many``
  through ``np.searchsorted`` + ``bincount``.
- **span tracing**: deterministic count-based sampling (every Nth
  produce / query — never ``random``, so differential runs stay
  reproducible) of the two flagship lifecycles: an *event* from
  ``DurablePipeline.produce`` → consumer pump → ``EventIngestor``
  apply → visible-at-watermark (true ingest-to-visibility latency,
  the paper's freshness knob), and a *query* through the serving
  tier's route cascade (cache / discovery / kernel / scan) with
  per-stage timings and candidate counts from ``last_plan``.
- **named spans**: ``span(name)`` binds a host span once; each entry
  is a profiler annotation ``icicle.<name>`` (the device trace's
  clock) and adds its seconds to ``span_seconds_total{span=<name>}``.
  Components open them at stage boundaries, per batch, never per
  record and never inside a jitted function.
- **exposition**: ``snapshot()`` (JSON-able programmatic scrape),
  ``render_prometheus()`` (text format: ``# HELP``/``# TYPE``,
  cumulative ``_bucket{le=...}``/``_sum``/``_count``), a bounded JSONL
  trace sink, and ``dashboard.telemetry_panel``.

Determinism contract: telemetry only OBSERVES — it never touches
arenas, watermarks, versions, or any serialized state, so the
differential/crash byte-identity suites hold with it enabled. Both
clocks are injectable (``clock`` for durations, ``wall`` for
timestamps) so telemetry's own tests are deterministic too.

``NullTelemetry`` is the zero-cost opt-out: every instrument it hands
out is a shared no-op. Components take ``telemetry=None`` and resolve
to the process default (``get_telemetry()`` / ``set_default``).
"""
from __future__ import annotations

import json
import threading
import time
from bisect import bisect_left
from collections import deque
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

#: default latency buckets (seconds): 100 µs .. 10 s, roughly 1-2-5
DEFAULT_LATENCY_BUCKETS = (
    1e-4, 2.5e-4, 5e-4, 1e-3, 2.5e-3, 5e-3, 1e-2, 2.5e-2, 5e-2,
    1e-1, 2.5e-1, 5e-1, 1.0, 2.5, 5.0, 10.0)

#: default size buckets (bytes): 1 KiB .. 4 GiB, powers of four
DEFAULT_SIZE_BUCKETS = tuple(float(4 ** k * 1024) for k in range(12))


class Counter:
    """Monotone counter. ``inc`` is one attribute add — hot-path safe."""

    __slots__ = ("value",)

    def __init__(self):
        self.value = 0

    def inc(self, n=1) -> None:
        self.value += n


class Gauge:
    """Point-in-time value. ``set_function`` registers a pull-time
    callback instead (read at snapshot/render), which is the zero-
    overhead choice for values derivable from existing state."""

    __slots__ = ("value", "fn")

    def __init__(self):
        self.value = 0
        self.fn: Optional[Callable[[], float]] = None

    def set(self, v) -> None:
        self.value = v

    def inc(self, n=1) -> None:
        self.value += n

    def dec(self, n=1) -> None:
        self.value -= n

    def set_function(self, fn: Callable[[], float]) -> None:
        self.fn = fn

    def read(self):
        return self.fn() if self.fn is not None else self.value


class Histogram:
    """Fixed-bucket histogram: ``edges`` are upper bounds (``le``
    semantics), plus an implicit +Inf bucket. Counts are a numpy int64
    vector; scalar observes go through ``bisect`` on a cached list."""

    __slots__ = ("edges", "_edges_list", "counts", "sum", "count")

    def __init__(self, buckets: Sequence[float]):
        self.edges = np.asarray(sorted(float(b) for b in buckets))
        self._edges_list = self.edges.tolist()
        self.counts = np.zeros(len(self.edges) + 1, np.int64)
        self.sum = 0.0
        self.count = 0

    def observe(self, v) -> None:
        self.counts[bisect_left(self._edges_list, v)] += 1
        self.sum += v
        self.count += 1

    def observe_many(self, values) -> None:
        vals = np.asarray(values, np.float64)
        if not len(vals):
            return
        idx = np.searchsorted(self.edges, vals, side="left")
        self.counts += np.bincount(idx, minlength=len(self.counts))
        self.sum += float(vals.sum())
        self.count += len(vals)

    def quantile(self, q: float) -> float:
        """Bucket-grain quantile estimate: the upper edge of the bucket
        where the cumulative count crosses ``q`` (the +Inf bucket
        reports the last finite edge). 0.0 with no observations."""
        if self.count == 0:
            return 0.0
        target = q * self.count
        cum = np.cumsum(self.counts)
        i = int(np.searchsorted(cum, target, side="left"))
        return float(self.edges[min(i, len(self.edges) - 1)])


class Family:
    """One named metric family: a set of instruments keyed by label
    values. ``labels(*values)`` returns (creating on first use) the
    child instrument; families declared without label names expose the
    instrument API directly on the family (the ``()`` child)."""

    _KINDS = {"counter": Counter, "gauge": Gauge, "histogram": Histogram}

    def __init__(self, kind: str, name: str, help: str,
                 label_names: Tuple[str, ...], lock: threading.Lock,
                 buckets: Optional[Sequence[float]] = None):
        self.kind = kind
        self.name = name
        self.help = help
        self.label_names = label_names
        self._lock = lock
        self._buckets = buckets
        self._children: Dict[Tuple, object] = {}

    def _make(self):
        if self.kind == "histogram":
            return Histogram(self._buckets or DEFAULT_LATENCY_BUCKETS)
        return self._KINDS[self.kind]()

    def labels(self, *values):
        key = tuple(str(v) for v in values)
        if len(key) != len(self.label_names):
            raise ValueError(
                f"{self.name} takes labels {self.label_names}, "
                f"got {len(key)} value(s)")
        child = self._children.get(key)
        if child is None:
            with self._lock:
                child = self._children.setdefault(key, self._make())
        return child

    # unlabeled convenience: the family IS its () child
    def _default(self):
        return self.labels()

    def inc(self, n=1) -> None:
        self._default().inc(n)

    def dec(self, n=1) -> None:
        self._default().dec(n)

    def set(self, v) -> None:
        self._default().set(v)

    def set_function(self, fn) -> None:
        self._default().set_function(fn)

    def observe(self, v) -> None:
        self._default().observe(v)

    def observe_many(self, values) -> None:
        self._default().observe_many(values)

    def quantile(self, q: float) -> float:
        return self._default().quantile(q)

    @property
    def value(self):
        return self._default().value

    def series(self) -> List[Dict]:
        out = []
        for key, child in sorted(self._children.items()):
            labels = dict(zip(self.label_names, key))
            if self.kind == "histogram":
                out.append({"labels": labels,
                            "buckets": child.edges.tolist(),
                            "counts": child.counts.tolist(),
                            "sum": float(child.sum),
                            "count": int(child.count)})
            elif self.kind == "gauge":
                out.append({"labels": labels, "value": child.read()})
            else:
                out.append({"labels": labels, "value": child.value})
        return out


class Span:
    """A named host span, made by ``Telemetry.span`` and held by its user.

    ``with span:`` opens a ``jax.profiler.TraceAnnotation`` named
    ``icicle.<name>`` (on the profiler's clock, so a trace shows it on
    the host lines beside the device ops, nested under whatever was
    open) and adds its duration on the telemetry clock to
    ``span_seconds_total{span=<name>}``. No per-call record is kept: a
    profiler trace holds those. Re-entrant and thread-safe: each thread
    keeps its own stack of open entries."""

    __slots__ = ("label", "_counter", "_clock", "_local", "_annotation")

    def __init__(self, name: str, counter, clock: Callable[[], float]):
        from jax.profiler import TraceAnnotation
        self.label = "icicle." + name
        self._counter = counter
        self._clock = clock
        self._local = threading.local()
        self._annotation = TraceAnnotation

    def __enter__(self):
        ann = self._annotation(self.label)
        ann.__enter__()
        try:
            stack = self._local.stack
        except AttributeError:
            stack = self._local.stack = []
        stack.append((ann, self._clock()))
        return self

    def __exit__(self, *exc):
        ann, t0 = self._local.stack.pop()
        self._counter.inc(self._clock() - t0)
        ann.__exit__(*exc)
        return False


class QueryTrace:
    """One sampled query span. ``stage(label)`` stamps a relative
    offset; ``finish(...)`` seals the trace into the telemetry's ring
    and JSONL sink."""

    __slots__ = ("_tel", "query", "_start", "wall", "stages", "_done")

    def __init__(self, tel: "Telemetry", query: str):
        self._tel = tel
        self.query = query
        self._start = tel.clock()
        self.wall = tel.wall()
        self.stages: List[List] = []
        self._done = False

    def stage(self, label: str) -> None:
        self.stages.append([label, self._tel.clock() - self._start])

    def finish(self, route: Optional[str] = None, cached: bool = False,
               candidates: Optional[int] = None, **extra) -> None:
        if self._done:
            return
        self._done = True
        total = self._tel.clock() - self._start
        trace = {"kind": "query", "query": self.query,
                 "wall_time": self.wall, "latency_s": total,
                 "route": route, "cached": bool(cached),
                 "candidates": candidates,
                 "stages": [list(s) for s in self.stages]}
        trace.update(extra)
        self._tel._finish_trace("queries", trace)


class Telemetry:
    """The process telemetry handle (see module docstring).

    ``event_sample_every`` / ``query_sample_every``: trace every Nth
    produce call / query (deterministic count-based sampling; <= 0
    disables that trace kind). ``trace_capacity`` bounds the in-memory
    completed-trace rings; ``max_pending_events`` bounds the pending
    event-trace table (oldest dropped — a produce whose events never
    reach the ingestor must not leak)."""

    enabled = True

    def __init__(self, clock: Callable[[], float] = time.perf_counter,
                 wall: Callable[[], float] = time.time,
                 event_sample_every: int = 128,
                 query_sample_every: int = 32,
                 trace_capacity: int = 256,
                 max_pending_events: int = 1024):
        self.clock = clock
        self.wall = wall
        self._lock = threading.Lock()
        self._families: Dict[str, Family] = {}
        self._collectors: List[Callable[[], None]] = []
        # tracing
        self._ev_every = int(event_sample_every)
        self._q_every = int(query_sample_every)
        self._ev_calls = 0
        self._q_calls = 0
        self._max_pending = int(max_pending_events)
        self._event_pending: Dict[int, Dict] = {}
        self.traces: Dict[str, deque] = {
            "events": deque(maxlen=int(trace_capacity)),
            "queries": deque(maxlen=int(trace_capacity))}
        # JSONL sink (bounded)
        self._sink = None
        self._sink_lock = threading.Lock()
        self._sink_limit = 0
        self._sink_written = 0
        self._sink_dropped = 0
        self._h_visibility = self.histogram(
            "event_visibility_latency_seconds",
            "produce -> visible-at-watermark latency of sampled events")

    # -- registry -------------------------------------------------------------

    def _family(self, kind: str, name: str, help: str,
                labels: Sequence[str],
                buckets: Optional[Sequence[float]] = None) -> Family:
        fam = self._families.get(name)
        if fam is not None:
            if fam.kind != kind:
                raise ValueError(
                    f"metric {name!r} already registered as {fam.kind}, "
                    f"requested {kind}")
            return fam
        with self._lock:
            fam = self._families.get(name)
            if fam is None:
                fam = Family(kind, name, help, tuple(labels), self._lock,
                             buckets=buckets)
                self._families[name] = fam
        return fam

    def counter(self, name: str, help: str = "",
                labels: Sequence[str] = ()) -> Family:
        return self._family("counter", name, help, labels)

    def gauge(self, name: str, help: str = "",
              labels: Sequence[str] = ()) -> Family:
        return self._family("gauge", name, help, labels)

    def histogram(self, name: str, help: str = "",
                  buckets: Optional[Sequence[float]] = None,
                  labels: Sequence[str] = ()) -> Family:
        return self._family("histogram", name, help, labels,
                            buckets=buckets)

    def span(self, name: str) -> Span:
        """A ``Span`` named ``name``. Bind it once and hold it: ``with
        held_span:`` then costs no lookup and no formatting per call.
        Spans bound under one name add to one series."""
        fam = self.counter("span_seconds_total",
                           "host seconds inside each named span",
                           labels=("span",))
        return Span(name, fam.labels(name), self.clock)

    def register_collector(self, fn: Callable[[], None]) -> None:
        """Run ``fn`` before every snapshot/render — the pull-time
        refresh hook for gauges derived from live state."""
        with self._lock:
            self._collectors.append(fn)

    # -- event tracing (produce -> pump -> apply -> visible) ------------------

    def trace_produce(self, seq: int) -> None:
        """Called once per produce micro-batch with its max changelog
        seq; every ``event_sample_every``-th call opens a pending trace
        completed by ``event_visible``."""
        self._ev_calls += 1
        if self._ev_every <= 0 or self._ev_calls % self._ev_every:
            return
        seq = int(seq)
        if seq <= 0:
            return
        pend = self._event_pending
        while len(pend) >= self._max_pending:
            pend.pop(next(iter(pend)), None)
        pend[seq] = {"seq": seq, "start": self.clock(),
                     "wall": self.wall(),
                     "stages": [["produce", 0.0]], "seen": {"produce"}}

    def event_stage(self, stage: str, upto_seq: int) -> None:
        """Stamp ``stage`` on every pending trace whose seq is at or
        below ``upto_seq`` (the pump/apply hooks pass their batch's max
        seq). One empty-dict check when nothing is being traced."""
        pend = self._event_pending
        if not pend:
            return
        t = self.clock()
        for seq, tr in pend.items():
            if seq <= upto_seq and stage not in tr["seen"]:
                tr["seen"].add(stage)
                tr["stages"].append([stage, t - tr["start"]])

    def event_visible(self, applied_seq: int) -> None:
        """Complete every pending trace at or below the applied
        watermark — called after each watermark advance, which is
        exactly when the event's effects become readable (buffered
        mode included: visibility IS the watermark advance)."""
        pend = self._event_pending
        if not pend:
            return
        t = self.clock()
        done = [s for s in pend if s <= applied_seq]
        for s in done:
            tr = pend.pop(s)
            total = t - tr["start"]
            tr["stages"].append(["visible", total])
            self._h_visibility.observe(total)
            self._finish_trace("events", {
                "kind": "event", "seq": tr["seq"],
                "wall_time": tr["wall"], "latency_s": total,
                "stages": tr["stages"]})

    # -- query tracing ---------------------------------------------------------

    def trace_query(self, query: str) -> Optional[QueryTrace]:
        """Every ``query_sample_every``-th call returns a live
        ``QueryTrace``; the rest return None (callers guard with
        ``if qt:`` — the unsampled path costs one modulo)."""
        self._q_calls += 1
        if self._q_every <= 0 or self._q_calls % self._q_every:
            return None
        return QueryTrace(self, query)

    # -- trace sinks -----------------------------------------------------------

    def open_trace_sink(self, path: str, limit: int = 10000) -> None:
        """Append completed traces to ``path`` as JSON lines, at most
        ``limit`` lines (a telemetry sink must never fill the disk the
        index checkpoints to — beyond the cap, traces are counted as
        dropped but still reach the in-memory rings)."""
        with self._sink_lock:
            if self._sink is not None:
                self._sink.close()
            self._sink = open(path, "a")
            self._sink_limit = int(limit)
            self._sink_written = 0
            self._sink_dropped = 0

    def close_trace_sink(self) -> None:
        with self._sink_lock:
            if self._sink is not None:
                self._sink.close()
                self._sink = None

    @property
    def sink_stats(self) -> Dict[str, int]:
        return {"written": self._sink_written,
                "dropped": self._sink_dropped}

    def _finish_trace(self, kind: str, trace: Dict) -> None:
        self.traces[kind].append(trace)
        if self._sink is None:
            return
        with self._sink_lock:
            if self._sink is None:
                return
            if self._sink_written >= self._sink_limit:
                self._sink_dropped += 1
                return
            self._sink.write(json.dumps(trace) + "\n")
            self._sink.flush()
            self._sink_written += 1

    # -- exposition ------------------------------------------------------------

    def _collect(self) -> None:
        with self._lock:
            collectors = list(self._collectors)
        for fn in collectors:
            fn()

    def snapshot(self, traces: bool = True) -> Dict:
        """Programmatic scrape: every family's series (JSON-able) plus
        the recent completed traces."""
        self._collect()
        with self._lock:
            fams = list(self._families.values())
        out = {"metrics": {
            f.name: {"type": f.kind, "help": f.help,
                     "label_names": list(f.label_names),
                     "series": f.series()}
            for f in fams}}
        if traces:
            out["traces"] = {"events": list(self.traces["events"]),
                             "queries": list(self.traces["queries"])}
        return out

    def render_prometheus(self) -> str:
        """Prometheus text exposition (version 0.0.4 shapes):
        ``# HELP``/``# TYPE`` per family, cumulative ``_bucket`` series
        with ``le`` labels plus ``_sum``/``_count`` for histograms."""
        self._collect()
        with self._lock:
            fams = sorted(self._families.values(), key=lambda f: f.name)
        lines: List[str] = []
        for f in fams:
            lines.append(f"# HELP {f.name} {f.help}")
            lines.append(f"# TYPE {f.name} {f.kind}")
            for s in f.series():
                base = _label_str(s["labels"])
                if f.kind != "histogram":
                    lines.append(f"{f.name}{base} {_fmt(s['value'])}")
                    continue
                cum = 0
                for edge, c in zip(s["buckets"], s["counts"]):
                    cum += c
                    lab = _label_str(dict(s["labels"], le=_fmt(edge)))
                    lines.append(f"{f.name}_bucket{lab} {cum}")
                cum += s["counts"][-1]
                lab = _label_str(dict(s["labels"], le="+Inf"))
                lines.append(f"{f.name}_bucket{lab} {cum}")
                lines.append(f"{f.name}_sum{base} {_fmt(s['sum'])}")
                lines.append(f"{f.name}_count{base} {s['count']}")
        return "\n".join(lines) + "\n"


def _fmt(v) -> str:
    if isinstance(v, float) and v == int(v) and abs(v) < 1e15:
        return str(int(v))
    return str(v)


def _label_str(labels: Dict[str, str]) -> str:
    if not labels:
        return ""
    esc = {k: str(v).replace("\\", r"\\").replace('"', r'\"')
           .replace("\n", r"\n") for k, v in labels.items()}
    inner = ",".join(f'{k}="{v}"' for k, v in esc.items())
    return "{" + inner + "}"


class _NullInstrument:
    """Shared no-op child: counter, gauge, and histogram API in one."""

    __slots__ = ()
    value = 0

    def labels(self, *a):
        return self

    def inc(self, n=1):
        pass

    def dec(self, n=1):
        pass

    def set(self, v):
        pass

    def set_function(self, fn):
        pass

    def observe(self, v):
        pass

    def observe_many(self, values):
        pass

    def quantile(self, q):
        return 0.0

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL = _NullInstrument()

#: public no-op instrument: a safe default for hot-path counter slots
#: bound before any telemetry handle is attached
NULL_INSTRUMENT = _NULL


class NullTelemetry:
    """Zero-cost opt-out: same surface as ``Telemetry``, every
    instrument (spans included) a shared no-op, every trace hook a
    pass. Its spans open no profiler annotation."""

    enabled = False
    clock = staticmethod(time.perf_counter)
    wall = staticmethod(time.time)

    def __init__(self, *a, **kw):
        self.traces = {"events": deque(maxlen=1), "queries": deque(maxlen=1)}

    def counter(self, name, help="", labels=()):
        return _NULL

    def gauge(self, name, help="", labels=()):
        return _NULL

    def histogram(self, name, help="", buckets=None, labels=()):
        return _NULL

    def span(self, name):
        return _NULL

    def register_collector(self, fn):
        pass

    def trace_produce(self, seq):
        pass

    def event_stage(self, stage, upto_seq):
        pass

    def event_visible(self, applied_seq):
        pass

    def trace_query(self, query):
        return None

    def open_trace_sink(self, path, limit=10000):
        pass

    def close_trace_sink(self):
        pass

    @property
    def sink_stats(self):
        return {"written": 0, "dropped": 0}

    def snapshot(self, traces=True):
        out = {"metrics": {}}
        if traces:
            out["traces"] = {"events": [], "queries": []}
        return out

    def render_prometheus(self):
        return ""


# -- the process default ------------------------------------------------------

_default_lock = threading.Lock()
_default: Optional[Telemetry] = None


def get_telemetry() -> Telemetry:
    """The process-default handle (created on first use, default ON —
    swap in a ``NullTelemetry`` via ``set_default`` to opt out)."""
    global _default
    if _default is None:
        with _default_lock:
            if _default is None:
                _default = Telemetry()
    return _default


def set_default(tel) -> object:
    """Install ``tel`` as the process default; returns the previous
    handle (tests swap and restore)."""
    global _default
    with _default_lock:
        prev = _default
        _default = tel
    return prev


def resolve(telemetry):
    """``telemetry`` if given, else the process default — the one
    resolution rule every component's ``telemetry=None`` knob uses."""
    return telemetry if telemetry is not None else get_telemetry()
