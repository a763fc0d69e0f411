"""Seconds ``SketchRegistry.open`` spent reservoir-sampling the tenant's
stream to bootstrap its partition plan: the program's
``kmatrix.registry.sample`` span keyed by the cell's tenant id.  The span
lies in set-up, before the window, so it is read from the whole ring.  A
program without the span, or a ring that has dropped it, reads nothing."""

SAMPLE = "kmatrix.registry.sample"


def read(ctx):
    from repro.obs.trace import get_trace_log

    tenant = getattr(ctx.cell, "tenant", None)
    log = get_trace_log()
    if tenant is None or not hasattr(log, "spans"):
        return None
    found = [s for s in log.spans()
             if s.name == SAMPLE and s.key == tenant.key.tenant_id]
    if not found:
        return None
    return (found[-1].t1_ns - found[-1].t0_ns) / 1e9
