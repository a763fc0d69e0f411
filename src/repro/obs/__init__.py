"""Unified telemetry tier (DESIGN.md §Observability).

- ``hub``: mergeable counters/gauges/log-bucketed histograms + Prometheus
  text exposition
- ``trace``: bounded event log with IDs propagated through queues, the
  wire codec, and publish adoption; ``TraceLog.span`` host-phase spans on
  the profiler's clock
- ``dashboard``: live terminal poller (``python -m repro.obs.dashboard``)
"""
from repro.obs.hub import (  # noqa: F401
    Counter, Gauge, Histogram, MetricsHub, LADDERS,
    get_hub, reset_hub,
    render_prometheus, quantile_from_state, merge_hist_states, hist_summary,
)
from repro.obs.trace import (  # noqa: F401
    Span, TraceLog, get_trace_log, reset_trace_log, new_trace_id,
)
from repro.obs.dump import (  # noqa: F401
    MetricsJsonDumper, scrape_payload,
)
