"""Share of its roofline the sketch ingest step reaches.

Least time: the bytes the kMatrix update rule needs over the chip's peak HBM
bandwidth (``bench/peaks.json``); bytes bound it, since the update does no
arithmetic to speak of.  The bytes come from the offered stream and the
configuration's depth alone (``kbench.stats.ingest_update_bytes``): read each
raw row once, and read-modify-write one pool cell and one conn cell per layer
for every distinct (src, dst) of each client batch.  So the same work is
counted whatever implements it (flat scatter, one-hot MXU, tiled).

Measured time: the mean device time of one execution of the jitted ingest
step of ``serving/snapshot.py`` (``_ingest_counted``, or ``_ingest`` without
dedup) in the trace.  The share is least time per client batch over that.
"""
import numpy as np

from kbench import stats
from kbench.readers import module_events

PATTERNS = [r"_ingest_counted", r"jit__ingest(\b|\()"]
MAX_BATCHES = 512


def read(ctx):
    if ctx.peaks is None:
        return None
    steps = module_events(ctx, PATTERNS)
    w = ctx.window
    ks = [k for k, b in enumerate(w.batches) if w.t_open <= b[1] < w.t_close]
    if not steps or not ks:
        return None
    ks = [ks[i] for i in np.linspace(0, len(ks) - 1,
                                     min(len(ks), MAX_BATCHES)).astype(int)]
    depth = int(ctx.cfg["sketch"]["depth"])
    lap = ctx.cell.lap
    need = []
    for k in ks:
        src, dst, wt = lap.client_batch_numpy(k)
        live = wt != 0
        need.append(stats.ingest_update_bytes(
            int(live.sum()), stats.distinct_pairs(src[live], dst[live]),
            depth))
    least_s = float(np.mean(need)) / ctx.peaks["hbm_bytes_per_s"]
    step_s = sum(e.end - e.start for e in steps) / len(steps) / 1e9
    return stats.roofline_share(least_s, step_s)
