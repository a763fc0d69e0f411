"""Share of the ingest worker's dedup dispatches, in %, whose every
pre-aggregated weight lies within the ingest kernel's one-pass bound
(``ONE_PASS_MAX_WEIGHT``), so that every width class of the dispatch took
one bfloat16 MXU pass and not the fp32 contraction: ``one_pass_dispatches``
over ``dedup_dispatches`` of ``IngestWorker.metrics_snapshot()``, both
counted since the tenant opened.  Nothing to read where no dispatch went
through the dedup path, or where the program counts no such dispatches."""


def read(ctx):
    snap = ctx.cell.handle.worker.metrics_snapshot()
    dispatches = snap.get("dedup_dispatches", 0)
    if not dispatches:
        return None
    return 100.0 * snap.get("one_pass_dispatches", 0) / dispatches
