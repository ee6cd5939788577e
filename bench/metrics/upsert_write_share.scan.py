"""Share of the window inside the program's ``index.upsert.write`` span:
the shards' writes after slot assignment in
``PrimaryIndex.upsert_batch`` (capacity, new paths, the version gate,
the column scatters, the entered mask; summed over shards). Read from the window's delta of
``span_seconds_total{span=index.upsert.write}``, in percent; None where the
program has no such span."""

KEY = "span_seconds_total|span=index.upsert.write"


def read(ctx):
    s = ctx["counters"].get(KEY, 0.0)
    w = ctx["window_s"]
    return 100.0 * s / w if s > 0 and w > 0 else None
