"""Share of the window inside the program's ``index.route.device`` span:
the hashshard call in ``ShardedPrimaryIndex._route_device``:
the pow2 pad, the byte matrix's copy to the device, the kernel, and
the hashes read back. Read from the window's delta of
``span_seconds_total{span=index.route.device}``, in percent; None where the
program has no such span."""

KEY = "span_seconds_total|span=index.route.device"


def read(ctx):
    s = ctx["counters"].get(KEY, 0.0)
    w = ctx["window_s"]
    return 100.0 * s / w if s > 0 and w > 0 else None
