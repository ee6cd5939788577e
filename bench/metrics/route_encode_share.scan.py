"""Share of the window inside the program's ``index.route.encode`` span:
the path strings packed into the hashshard kernel's byte
matrix (``encode_strings_np`` in ``ShardedPrimaryIndex._route_device``). Read from the window's delta of
``span_seconds_total{span=index.route.encode}``, in percent; None where the
program has no such span."""

KEY = "span_seconds_total|span=index.route.encode"


def read(ctx):
    s = ctx["counters"].get(KEY, 0.0)
    w = ctx["window_s"]
    return 100.0 * s / w if s > 0 and w > 0 else None
