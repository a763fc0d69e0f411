"""Share of the ingest worker's publishes that stamped their epoch from the
host's own edge count, in %: of the ``kmatrix.worker.publish`` spans that
started in the window, those whose epoch key (on the same thread) has a
``kmatrix.snapshot.publish_host_count`` span.  The others read the count
back from the device (``kmatrix.snapshot.publish_sync``); a program that
has no host-count path reads 0."""
from kbench.spans import window_spans

HOST_COUNT = "kmatrix.snapshot.publish_host_count"


def read(ctx):
    spans = window_spans(ctx)
    if spans is None:
        return None
    pubs = {(s.thread, s.key) for s in spans
            if s.name == "kmatrix.worker.publish"}
    if not pubs:
        return None
    from repro.obs.trace import get_trace_log

    # the whole ring, not just the window: a publish that opened just
    # before the window closed has its host-count span just after
    counted = {(s.thread, s.key) for s in get_trace_log().spans()
               if s.name == HOST_COUNT}
    return 100.0 * len(pubs & counted) / len(pubs)
