"""Share of the ingest worker's dispatched rows, in %, that took the
sketch's exact scatter fallback because their partition was past its width
class's dispatch capacity: ``overflow_edges`` over ``dedup_unique_rows`` of
``IngestWorker.metrics_snapshot()``, both counted since the tenant opened.
Nothing to read where no row was dispatched through the dedup path."""


def read(ctx):
    snap = ctx.cell.handle.worker.metrics_snapshot()
    rows = snap.get("dedup_unique_rows", 0)
    if not rows:
        return None
    return 100.0 * snap.get("overflow_edges", 0) / rows
