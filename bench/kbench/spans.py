"""The program's own host spans in the window, and how they line up with the
device's idle time.

The program times its host phases with ``repro.obs.trace.TraceLog.span``:
``Span(name, t0_ns, t1_ns, key, thread)`` records in a bounded ring, on
``time.perf_counter_ns``, the clock of ``kbench.drive.Window``.  A program
that keeps no such ring (no ``spans`` on its trace log) gives nothing to
read, and neither does a ring that dropped spans the window needs: no
partial numbers.

To line spans up with the device trace, the ``bench.window`` annotation is
the anchor: ``drive.py`` enters it and then reads ``perf_counter`` for
``t_open``, so ``trace.t0`` and ``t_open`` are the same instant on the two
clocks.
"""
from __future__ import annotations

import heapq

from kbench import trace as tracing

NO_SPAN = "no span"
# The worker's own host work: the device idles behind these because the
# host has not yet handed it the next dispatch.  ``publish`` counts only
# outside its child ``publish_sync``, which waits on the device.
HOST_WORK = ("kmatrix.worker.dedup", "kmatrix.worker.stage",
             "kmatrix.worker.dispatch", "kmatrix.worker.reservoir",
             "kmatrix.worker.publish")


def window_spans(ctx) -> list | None:
    """Spans that started inside ``[t_open, t_close)``, or None."""
    from repro.obs.trace import get_trace_log

    log = get_trace_log()
    if not hasattr(log, "spans"):
        return None
    spans = log.spans()
    t_open = ctx.window.t_open * 1e9
    t_close = ctx.window.t_close * 1e9
    # the ring drops its oldest (first ended) span first: while the oldest
    # kept span ended before the window opened, none of the window's went
    if log.spans_dropped and (not spans or spans[0].t1_ns >= t_open):
        return None
    return [s for s in spans if t_open <= s.t0_ns < t_close]


def mean_per_key_ms(ctx, names) -> float | None:
    """Mean over units of work (thread, key) of the summed time of the
    named spans of each, in milliseconds."""
    spans = window_spans(ctx)
    if spans is None:
        return None
    per: dict = {}
    for s in spans:
        if s.name in names:
            k = (s.thread, s.key)
            per[k] = per.get(k, 0) + (s.t1_ns - s.t0_ns)
    if not per:
        return None
    return sum(per.values()) / len(per) / 1e6


def innermost(spans) -> list:
    """Cut ``(start, end, name)`` spans into disjoint, sorted pieces, each
    named by the innermost span that covers it: the one that started last
    (the shorter one on a tie)."""
    starts = sorted(spans)
    bounds = sorted({t for s, e, _ in spans for t in (s, e)})
    active: list = []  # (-start, end, name)
    out = []
    i = 0
    for a, b in zip(bounds, bounds[1:]):
        while i < len(starts) and starts[i][0] <= a:
            s, e, name = starts[i]
            heapq.heappush(active, (-s, e, name))
            i += 1
        while active and active[0][1] <= a:
            heapq.heappop(active)
        if active:
            name = active[0][2]
            if out and out[-1][2] == name and out[-1][1] == a:
                out[-1] = (out[-1][0], b, name)
            else:
                out.append((a, b, name))
    return out


def overlap_by_name(gaps, pieces) -> dict:
    """Nanoseconds of each sorted disjoint ``(start, end)`` gap list that
    the sorted disjoint named pieces cover, by name."""
    out: dict = {}
    j = 0
    for a, b in gaps:
        while j < len(pieces) and pieces[j][1] <= a:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][0] < b:
            o = min(b, pieces[k][1]) - max(a, pieces[k][0])
            if o > 0:
                out[pieces[k][2]] = out.get(pieces[k][2], 0.0) + o
            k += 1
    return out


def to_trace_clock(ctx, spans) -> list:
    """``(start, end, name)`` of each span on the profiler's clock."""
    offset = ctx.trace.t0 - ctx.window.t_open * 1e9
    return [(s.t0_ns + offset, s.t1_ns + offset, s.name) for s in spans]


def idle_split(ctx) -> dict | None:
    """Seconds of device idle time in the traced window, by the innermost
    span that covers it (``NO_SPAN`` where none does), averaged over the
    devices as ``TraceData.busy_s`` averages busy time.  The values add up
    to the window's idle time."""
    tr = ctx.trace
    if tr is None or not tr.device or tr.window_s <= 0:
        return None
    spans = window_spans(ctx)
    if not spans:
        return None
    pieces = innermost(to_trace_clock(ctx, spans))
    per_plane: dict = {}
    for e in tr.line(tracing.OPS_LINE) or tr.device:
        per_plane.setdefault(e.plane, []).append((e.start, e.end))
    split: dict = {}
    for intervals in per_plane.values():
        gaps = tracing.idle_gaps(intervals, tr.t0, tr.t1)
        named = overlap_by_name(gaps, pieces)
        named[NO_SPAN] = sum(b - a for a, b in gaps) - sum(named.values())
        for name, ns in named.items():
            split[name] = split.get(name, 0.0) + ns
    n = max(tr.n_devices, 1)
    return {name: ns / n / 1e9 for name, ns in split.items()}


def longest_gaps(ctx, top: int = 10) -> list:
    """The longest idle gaps of the first device: ``[label, seconds,
    seconds after the window opened]``, the label naming the spans over
    the gap with their milliseconds in it, most time first."""
    tr = ctx.trace
    spans = window_spans(ctx)
    if tr is None or spans is None:
        return []
    ops = tr.line(tracing.OPS_LINE) or tr.device
    plane = ops[0].plane if ops else ""
    gaps = tracing.idle_gaps([(e.start, e.end) for e in ops
                              if e.plane == plane], tr.t0, tr.t1)
    pieces = innermost(to_trace_clock(ctx, spans))
    out = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        named = overlap_by_name([(a, b)], pieces)
        named[NO_SPAN] = (b - a) - sum(named.values())
        label = " + ".join(f"{k} {v / 1e6:.3f} ms" for k, v in
                           sorted(named.items(), key=lambda kv: -kv[1])
                           if v > 0)
        out.append([label, (b - a) / 1e9, (a - tr.t0) / 1e9])
    return out


def worker_coverage(ctx) -> float | None:
    """Share of the window that the spans of the ingest worker threads
    cover, each thread alone, averaged over those threads."""
    spans = window_spans(ctx)
    if not spans:
        return None
    t_open, t_close = ctx.window.t_open * 1e9, ctx.window.t_close * 1e9
    per_thread: dict = {}
    for s in spans:
        if s.name.startswith("kmatrix.worker."):
            per_thread.setdefault(s.thread, []).append((s.t0_ns, s.t1_ns))
    if not per_thread:
        return None
    return sum(tracing.union_ns(iv, t_open, t_close)
               for iv in per_thread.values()) \
        / len(per_thread) / (t_close - t_open)
