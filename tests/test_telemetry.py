"""Telemetry subsystem tests (ISSUE 10 tentpole).

- registry: counter/gauge/histogram semantics, labeled families,
  collision detection, pull-time gauge callbacks;
- exposition: snapshot() JSON-ability, Prometheus text shapes
  (cumulative ``_bucket``/``+Inf``/``_sum``/``_count``), bounded JSONL
  trace sink;
- span tracing: one sampled EVENT trace demonstrably spanning
  produce -> pump -> apply -> visible with per-stage timings, one
  QUERY trace recording route + per-stage latency (both under
  injected deterministic clocks);
- determinism: index state is byte-identical whether the pipeline
  runs under a full Telemetry or a NullTelemetry;
- named spans: summed seconds per span on the telemetry clock, nesting
  and re-entry, the NullTelemetry no-op, and the spans' place on the
  profiler's host timeline.
"""
import glob
import json
import os

import numpy as np
import pytest

from repro.core import events as ev
from repro.core import snapshot as snap
from repro.core.dashboard import telemetry_panel
from repro.core.event_ingest import EventIngestor, IngestConfig
from repro.core.eventlog import EventLog
from repro.core.index import AggregateIndex, PrimaryIndex
from repro.core.query_service import QueryService
from repro.core.stream_pipeline import DurablePipeline
from repro.core.telemetry import (NULL_INSTRUMENT, NullTelemetry, Telemetry,
                                  get_telemetry, resolve, set_default)

PCFG = snap.PipelineConfig(n_users=8, n_groups=4, n_dirs=16)


class FakeClock:
    """Deterministic monotone clock: every read advances 1 ms."""

    def __init__(self, start=0.0, step=1e-3):
        self.t = start
        self.step = step

    def __call__(self):
        self.t += self.step
        return self.t


def _tel(**kw):
    kw.setdefault("clock", FakeClock())
    kw.setdefault("wall", FakeClock(start=1_700_000_000.0))
    return Telemetry(**kw)


def _create_batch(fids):
    b = ev.empty_batch(len(fids))
    f = np.asarray(fids)
    b["seq"] = f.astype(np.int64)
    b["etype"][:] = ev.E_CREAT
    b["fid"] = f.astype(np.int32)
    b["parent_fid"][:] = 0
    b["has_stat"][:] = 1
    b["size"] = (f % 97).astype(np.float32)
    b["mtime"] = (f % 31).astype(np.float32)
    b["uid"] = (f % 5 + 1).astype(np.int32)
    b["gid"] = (f % 3 + 1).astype(np.int32)
    return b


def _pipeline(tel, mode="eager"):
    log = EventLog(telemetry=tel)
    primary = PrimaryIndex()
    ing = EventIngestor(
        IngestConfig(mode=mode, pad_to=64, update_aggregates=False),
        PCFG, primary, AggregateIndex(), names={0: "fs"}, telemetry=tel)
    pipe = DurablePipeline(log, ing, batch_size=32, telemetry=tel)
    return log, primary, ing, pipe


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

def test_counter_gauge_histogram_basics():
    tel = _tel()
    c = tel.counter("c_total", "a counter")
    c.inc()
    c.inc(4)
    assert c.value == 5
    g = tel.gauge("g", "a gauge")
    g.set(7)
    g.dec(2)
    assert g.labels().read() == 5
    h = tel.histogram("h_seconds", "a histogram", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(50.0)                     # lands in +Inf
    child = h.labels()
    assert child.count == 3
    assert child.counts.tolist() == [1, 1, 1]
    assert child.sum == pytest.approx(50.55)
    assert h.quantile(0.5) == 1.0       # bucket-grain upper edge


def test_labeled_families_and_collisions():
    tel = _tel()
    fam = tel.counter("routed_total", "per-shard", labels=("shard",))
    fam.labels("0").inc(3)
    fam.labels("1").inc()
    assert fam.labels(0).value == 3     # values stringify
    series = fam.series()
    assert [s["labels"] for s in series] == [{"shard": "0"}, {"shard": "1"}]
    # re-registration returns the SAME family; kind mismatch raises
    assert tel.counter("routed_total") is fam
    with pytest.raises(ValueError):
        tel.gauge("routed_total")
    with pytest.raises(ValueError):
        fam.labels("a", "b")            # wrong label arity


def test_gauge_pull_callback_reads_at_snapshot_time():
    tel = _tel()
    state = {"v": 1}
    tel.gauge("live_g", "pull").set_function(lambda: state["v"])
    assert tel.snapshot(traces=False)[
        "metrics"]["live_g"]["series"][0]["value"] == 1
    state["v"] = 42
    assert tel.snapshot(traces=False)[
        "metrics"]["live_g"]["series"][0]["value"] == 42


def test_histogram_observe_many_matches_scalar_path():
    tel = _tel()
    a = tel.histogram("a_s", buckets=(1.0, 2.0, 4.0)).labels()
    b = tel.histogram("b_s", buckets=(1.0, 2.0, 4.0)).labels()
    vals = [0.5, 1.0, 1.5, 3.0, 9.0, 2.0]
    for v in vals:
        a.observe(v)
    b.observe_many(vals)
    assert a.counts.tolist() == b.counts.tolist()
    assert a.sum == pytest.approx(b.sum)
    assert a.count == b.count


# ---------------------------------------------------------------------------
# exposition
# ---------------------------------------------------------------------------

def test_snapshot_is_json_able_and_prometheus_renders():
    tel = _tel()
    tel.counter("x_total", "help text", labels=("k",)).labels("v").inc(2)
    tel.histogram("lat_seconds", "lat", buckets=(0.1, 1.0)).observe(0.5)
    snap_ = tel.snapshot()
    json.dumps(snap_)                   # must not raise
    text = tel.render_prometheus()
    assert "# HELP x_total help text" in text
    assert "# TYPE x_total counter" in text
    assert 'x_total{k="v"} 2' in text
    # cumulative buckets + +Inf + _sum/_count
    assert 'lat_seconds_bucket{le="0.1"} 0' in text
    assert 'lat_seconds_bucket{le="1"} 1' in text
    assert 'lat_seconds_bucket{le="+Inf"} 1' in text
    assert "lat_seconds_sum 0.5" in text
    assert "lat_seconds_count 1" in text


def test_jsonl_sink_is_bounded(tmp_path):
    tel = _tel(query_sample_every=1)
    p = str(tmp_path / "traces.jsonl")
    tel.open_trace_sink(p, limit=3)
    for i in range(5):
        qt = tel.trace_query(f"q{i}")
        qt.finish(route="scan")
    tel.close_trace_sink()
    lines = [json.loads(ln) for ln in open(p)]
    assert len(lines) == 3              # capped
    assert tel.sink_stats == {"written": 3, "dropped": 2}
    assert len(tel.traces["queries"]) == 5   # ring still sees all


# ---------------------------------------------------------------------------
# default handle / opt-out
# ---------------------------------------------------------------------------

@pytest.fixture
def swapped_default():
    tel = _tel()
    prev = set_default(tel)
    yield tel
    set_default(prev)


def test_default_handle_swap_and_resolve(swapped_default):
    assert get_telemetry() is swapped_default
    assert resolve(None) is swapped_default
    other = NullTelemetry()
    assert resolve(other) is other


def test_null_telemetry_is_inert():
    null = NullTelemetry()
    c = null.counter("whatever")
    c.inc()
    c.labels("a", "b").observe(1.0)     # one shared no-op child
    assert c is NULL_INSTRUMENT
    assert null.trace_query("q") is None
    null.trace_produce(1)
    null.event_stage("pump", 1)
    null.event_visible(1)
    assert null.snapshot() == {"metrics": {},
                               "traces": {"events": [], "queries": []}}
    assert null.render_prometheus() == ""


# ---------------------------------------------------------------------------
# event tracing end to end: produce -> pump -> apply -> visible
# ---------------------------------------------------------------------------

def test_event_trace_spans_produce_to_visible():
    tel = _tel(event_sample_every=1)
    log, primary, ing, pipe = _pipeline(tel)
    pipe.produce(_create_batch([1, 2, 3]))
    pipe.pump()
    pipe.flush()                        # apply the held seq-aligned tail
    traces = list(tel.traces["events"])
    assert len(traces) == 1
    tr = traces[0]
    assert tr["kind"] == "event" and tr["seq"] == 3
    stages = [s for s, _ in tr["stages"]]
    assert stages == ["produce", "pump", "apply", "visible"]
    # per-stage offsets are monotone non-decreasing and deterministic
    # under the injected 1 ms fake clock
    offsets = [t for _, t in tr["stages"]]
    assert offsets[0] == 0.0
    assert all(b >= a for a, b in zip(offsets, offsets[1:]))
    assert tr["latency_s"] == pytest.approx(offsets[-1])
    assert tr["latency_s"] > 0
    # the visibility histogram observed it
    h = tel.histogram("event_visibility_latency_seconds").labels()
    assert h.count == 1
    # and the record landed in the index (trace only observed)
    assert len(primary) == 3


def test_event_trace_sampling_every_nth():
    tel = _tel(event_sample_every=2)
    log, primary, ing, pipe = _pipeline(tel)
    for i in range(4):
        pipe.produce(_create_batch([10 * i + 1, 10 * i + 2]))
        pipe.pump()
    assert len(tel.traces["events"]) == 2    # calls 2 and 4


def test_buffered_mode_trace_completes_at_flush():
    tel = _tel(event_sample_every=1)
    log, primary, ing, pipe = _pipeline(tel, mode="buffered")
    pipe.produce(_create_batch([1, 2]))
    pipe.pump()                         # buffered: applied only at flush
    assert len(tel.traces["events"]) == 0
    pipe.flush()
    traces = list(tel.traces["events"])
    assert len(traces) == 1
    assert [s for s, _ in traces[0]["stages"]] == [
        "produce", "pump", "apply", "visible"]


def test_pending_event_traces_are_bounded():
    tel = _tel(event_sample_every=1, max_pending_events=4)
    for seq in range(1, 10):
        tel.trace_produce(seq)
    assert len(tel._event_pending) == 4
    tel.event_visible(100)
    assert len(tel.traces["events"]) == 4


# ---------------------------------------------------------------------------
# query tracing through the serving tier
# ---------------------------------------------------------------------------

def _service(tel):
    primary = PrimaryIndex()
    for i in range(8):
        primary.upsert(f"/fs/f{i}", {"size": float(i) * 1e9, "uid": i % 3,
                                     "gid": 0, "atime": 0.0, "mtime": 0.0,
                                     "mode": 0o644}, version=1)
    return QueryService(primary, AggregateIndex(), use_kernels=False,
                        telemetry=tel)


def test_query_trace_records_route_and_stages():
    tel = _tel(query_sample_every=1)
    svc = _service(tel)
    svc.query("world_writable")
    traces = list(tel.traces["queries"])
    assert len(traces) == 1
    tr = traces[0]
    assert tr["kind"] == "query" and tr["query"] == "world_writable"
    assert tr["route"] == "scan" and tr["cached"] is False
    assert [s for s, _ in tr["stages"]] == ["acquire_snapshot", "execute"]
    assert all(t > 0 for _, t in tr["stages"])
    assert tr["latency_s"] > 0
    # second identical query is a cache hit -> route "cache"
    svc.query("world_writable")
    assert list(tel.traces["queries"])[-1]["route"] == "cache"
    # the per-query latency histogram saw both
    fam = tel.histogram("service_query_seconds")
    assert fam.labels("world_writable").count == 2
    svc.close()


def test_query_service_counters_hits_misses():
    tel = _tel()
    svc = _service(tel)
    svc.query("stat", "/fs/f1")
    svc.query("stat", "/fs/f1")
    svc.query("stat", "/fs/f2")
    assert tel.counter("service_cache_misses_total").value == 2
    assert tel.counter("service_cache_hits_total").value == 1
    svc.close()


def test_dashboard_panel_renders():
    tel = _tel(query_sample_every=1, event_sample_every=1)
    log, primary, ing, pipe = _pipeline(tel)
    pipe.produce(_create_batch([1, 2]))
    pipe.pump()
    pipe.flush()
    svc = QueryService(primary, AggregateIndex(), ingestor=ing,
                       use_kernels=False, telemetry=tel)
    svc.query("world_writable")
    panel = telemetry_panel(tel)
    assert "== telemetry ==" in panel
    assert "ingest->visible" in panel
    assert "trace event seq=2" in panel
    assert "trace query world_writable" in panel
    svc.close()


# ---------------------------------------------------------------------------
# determinism: telemetry only observes
# ---------------------------------------------------------------------------

def test_index_state_identical_with_and_without_telemetry():
    states = []
    for tel in (_tel(event_sample_every=1, query_sample_every=1),
                NullTelemetry()):
        log, primary, ing, pipe = _pipeline(tel)
        pipe.produce(_create_batch([1, 2, 3]))
        pipe.pump()
        pipe.produce(_create_batch([4, 5]))
        pipe.pump()
        states.append(primary.state_dict())
        metrics = dict(ing.metrics)
        states.append(metrics)
    assert _canon(states[0]) == _canon(states[2])
    assert states[1] == states[3]


def _canon(obj):
    if isinstance(obj, dict):
        return {k: _canon(v) for k, v in sorted(obj.items())}
    if isinstance(obj, (list, tuple)):
        return [_canon(v) for v in obj]
    if isinstance(obj, np.ndarray):
        return obj.tolist()
    if isinstance(obj, bytes):
        return obj.hex()
    return obj


# ---------------------------------------------------------------------------
# named spans
# ---------------------------------------------------------------------------

def _span_seconds(tel, name):
    return tel.counter("span_seconds_total").labels(name).value


@pytest.mark.parametrize("calls", [1, 3])
def test_span_sums_seconds_on_the_telemetry_clock(calls):
    tel = _tel()                        # every clock read advances 1 ms
    sp, again = tel.span("stage"), tel.span("stage")
    for _ in range(calls):
        with sp:
            pass
    with again:                         # one name, one series
        pass
    assert _span_seconds(tel, "stage") == pytest.approx((calls + 1) * 1e-3)
    series = tel.snapshot(traces=False)["metrics"]["span_seconds_total"]
    assert series["series"] == [{"labels": {"span": "stage"},
                                 "value": pytest.approx((calls + 1) * 1e-3)}]
    assert 'span_seconds_total{span="stage"}' in tel.render_prometheus()


def test_spans_nest_and_reenter():
    tel = _tel()
    outer, inner = tel.span("outer"), tel.span("inner")
    # clock reads: outer 1, inner 2, inner 3 | 4, 5, outer 6 (ms)
    with outer:
        with inner:
            with inner:                 # a re-entry keeps its own start
                pass
    assert _span_seconds(tel, "inner") == pytest.approx(1e-3 + 3e-3)
    assert _span_seconds(tel, "outer") == pytest.approx(5e-3)


def test_span_exits_on_exception():
    tel = _tel()
    sp = tel.span("fails")
    with pytest.raises(RuntimeError):
        with sp:
            raise RuntimeError("boom")
    assert _span_seconds(tel, "fails") == pytest.approx(1e-3)
    with sp:                            # the stack was unwound
        pass
    assert _span_seconds(tel, "fails") == pytest.approx(2e-3)


def test_null_telemetry_span_is_a_shared_noop(monkeypatch):
    import jax

    def no_annotation(*a, **kw):
        raise AssertionError("NullTelemetry opened a profiler annotation")
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", no_annotation)
    null = NullTelemetry()
    sp = null.span("index.route.encode")
    assert sp is NULL_INSTRUMENT and null.span("other") is sp
    with sp:
        with sp:
            pass
    assert null.snapshot(traces=False) == {"metrics": {}}


def test_spans_sit_on_the_profiler_host_timeline(tmp_path):
    """A CPU profiler trace holds the ``icicle.*`` annotations nested
    inside an outer annotation on one host line: the spans share the
    clock every host and device event of a trace is on."""
    import jax
    from jax.profiler import ProfileData
    tel = Telemetry()
    a, b = tel.span("a"), tel.span("b")
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("outer"):
            with a:
                with b:
                    pass
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    want = {"outer", "icicle.a", "icicle.b"}
    found = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name in want:
                    found[e.name] = (plane.name, line.name,
                                     e.start_ns, e.end_ns)
    assert set(found) == want
    assert {v[:2] for v in found.values()} == {found["outer"][:2]}
    assert found["outer"][0] == "/host:CPU"
    o, sa, sb = (found[k][2:] for k in ("outer", "icicle.a", "icicle.b"))
    assert o[0] <= sa[0] <= sb[0] <= sb[1] <= sa[1] <= o[1]
    assert _span_seconds(tel, "a") >= _span_seconds(tel, "b") > 0
