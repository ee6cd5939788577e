"""Share of the window inside the program's ``index.upsert.assign`` span:
the shards' slot-map probes (``slot_map.assign`` in
``PrimaryIndex.upsert_batch``, summed over shards). Read from the window's delta of
``span_seconds_total{span=index.upsert.assign}``, in percent; None where the
program has no such span."""

KEY = "span_seconds_total|span=index.upsert.assign"


def read(ctx):
    s = ctx["counters"].get(KEY, 0.0)
    w = ctx["window_s"]
    return 100.0 * s / w if s > 0 and w > 0 else None
