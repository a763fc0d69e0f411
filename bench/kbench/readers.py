"""Helpers the per-layer metric readers share (``bench/metrics/*.py``)."""
from __future__ import annotations

from kbench import trace as tracing


def idle_share(ctx) -> float | None:
    """Percent of the traced window in which no device operation ran."""
    tr = ctx.trace
    if tr is None or tr.window_s <= 0 or not tr.device:
        return None
    return 100.0 * (1.0 - tr.busy_s() / tr.window_s)


def module_events(ctx, patterns) -> list:
    """Executions of the jitted programs whose names match ``patterns``,
    started inside the traced window."""
    tr = ctx.trace
    if tr is None:
        return []
    mods = tr.line(tracing.MODULES_LINE) or tr.device
    return tracing.in_window(tracing.matching(mods, patterns), tr.t0, tr.t1)


def mean_ms(events) -> float | None:
    if not events:
        return None
    return sum(e.end - e.start for e in events) / len(events) / 1e6
