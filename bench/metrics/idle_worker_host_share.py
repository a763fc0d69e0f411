"""Share of the traced window in which the device was idle while the ingest
worker was inside one of its own host-work spans (``kbench.spans.HOST_WORK``:
dedup, stage, dispatch, reservoir, and publish outside its device wait).

The device's idle gaps come from the ``XLA Ops`` line of the trace; the
program's spans are put on the trace's clock with the ``bench.window``
annotation as the anchor.  The split of all idle time by span name, the
part no span covers, how much of the window the worker's spans cover, and
the longest gaps with the spans over them go to the log.
"""
from kbench.drive import log
from kbench.spans import (HOST_WORK, idle_split, longest_gaps,
                          worker_coverage)


def read(ctx):
    split = idle_split(ctx)
    if split is None:
        return None
    window_s = ctx.trace.window_s
    parts = ", ".join(f"{name} {1e3 * s:.3f} ms ({100 * s / window_s:.3f}%)"
                      for name, s in sorted(split.items(),
                                            key=lambda kv: -kv[1]))
    log(f"idle split by span over a {window_s:.3f} s window: {parts}; "
        f"total {100 * sum(split.values()) / window_s:.3f}%")
    cover = worker_coverage(ctx)
    if cover is not None:
        log(f"worker spans cover {100 * cover:.2f}% of the window")
    log(f"longest idle gaps by span: {longest_gaps(ctx)}")
    return 100.0 * sum(split.get(name, 0.0) for name in HOST_WORK) \
        / window_s
