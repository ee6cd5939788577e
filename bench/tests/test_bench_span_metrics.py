"""The per-layer metrics that read the program's stage spans inside the
index's route and upsert: each reader's share of the window, None where
the program has no such span, and the counter keys as the harness
reads them from a real route and upsert."""
import os
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import harness  # noqa: E402

SPAN_METRICS = [
    ("route_encode_share.scan", "index.route.encode"),
    ("route_device_share.scan", "index.route.device"),
    ("upsert_split_share.scan", "index.upsert.split"),
    ("upsert_assign_share.scan", "index.upsert.assign"),
    ("upsert_write_share.scan", "index.upsert.write"),
]


def _key(span):
    return f"span_seconds_total|span={span}"


@pytest.mark.parametrize("metric,span", SPAN_METRICS)
def test_share_of_the_window(metric, span):
    read = harness.metric_reader(metric)
    ctx = {"counters": {_key(span): 1.5, _key("other"): 9.0},
           "spans": {}, "window_s": 20.0}
    assert read(ctx) == pytest.approx(7.5)


@pytest.mark.parametrize("metric,span", SPAN_METRICS)
@pytest.mark.parametrize("counters", [{}, {"shard_mutation_records_total"
                                           "|op=upsert,shard=0": 5.0}])
def test_none_without_the_span(metric, span, counters):
    read = harness.metric_reader(metric)
    assert read({"counters": counters, "spans": {}, "window_s": 20.0}) \
        is None
    zero = dict(counters, **{_key(span): 0.0})
    assert read({"counters": zero, "spans": {}, "window_s": 20.0}) is None


def test_readers_find_the_program_counters():
    """A route and an upsert under the process telemetry, read as the
    scan run reads them (``harness.counters`` before and after)."""
    from repro.core.sharded_index import ShardedPrimaryIndex
    from repro.core.telemetry import Telemetry, set_default
    prev = set_default(Telemetry())
    try:
        idx = ShardedPrimaryIndex(4, kernel_route_min=8, route_width=64)
        paths = [f"/fs/d{i % 5}/f{i}" for i in range(200)]
        before = harness.counters()
        h, _ = idx.route(paths)
        idx.upsert_batch(paths, {"size": np.ones(200, np.float32)},
                         np.ones(200, np.int64), hashes=h)
        ctx = {"counters": harness.delta(harness.counters(), before),
               "spans": {}, "window_s": 1.0}
    finally:
        set_default(prev)
    for metric, _ in SPAN_METRICS:
        got = harness.metric_reader(metric)(ctx)
        assert got is not None and 0 < got < 100, metric
