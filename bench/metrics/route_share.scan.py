"""Share of the window spent in the benchmark's ``bench.route`` spans
(``ShardedPrimaryIndex.route`` calls), in percent."""


def read(ctx):
    s = ctx["spans"].get("route", 0.0)
    w = ctx["window_s"]
    return 100.0 * s / w if s > 0 and w > 0 else None
