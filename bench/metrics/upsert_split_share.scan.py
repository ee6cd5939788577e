"""Share of the window inside the program's ``index.upsert.split`` span:
``ShardedPrimaryIndex.upsert_batch``'s shard split: shard ids
from the given hashes, the stable sort, and the gathers of paths,
versions, hashes and fields. Read from the window's delta of
``span_seconds_total{span=index.upsert.split}``, in percent; None where the
program has no such span."""

KEY = "span_seconds_total|span=index.upsert.split"


def read(ctx):
    s = ctx["counters"].get(KEY, 0.0)
    w = ctx["window_s"]
    return 100.0 * s / w if s > 0 and w > 0 else None
