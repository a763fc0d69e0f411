"""Raw rows over dispatched rows of the ingest worker's exact duplicate-edge
pre-aggregation in the window (``WorkerMetrics`` dedup counters)."""


def read(ctx):
    w = ctx.window
    raw = w.dedup_end[0] - w.dedup_start[0]
    unique = w.dedup_end[1] - w.dedup_start[1]
    if unique <= 0:
        return None
    return raw / unique
