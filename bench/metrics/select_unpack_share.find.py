"""Share of the window inside the program's ``query.select.unpack`` span:
the bitmap unpacked into candidate slots (``bitmap_slots``), summed over
shards. Read from the window's delta of
``span_seconds_total{span=query.select.unpack}``, in percent; None where
the program has no such span."""

KEY = "span_seconds_total|span=query.select.unpack"


def read(ctx):
    s = ctx["counters"].get(KEY, 0.0)
    w = ctx["window_s"]
    return 100.0 * s / w if s > 0 and w > 0 else None
