"""Share of the window spent in the benchmark's ``bench.upsert`` spans
(``ShardedPrimaryIndex.upsert_batch`` calls), in percent."""


def read(ctx):
    s = ctx["spans"].get("upsert", 0.0)
    w = ctx["window_s"]
    return 100.0 * s / w if s > 0 and w > 0 else None
