"""Bounded trace-span log: IDs minted at the edges, events everywhere.

A trace ID is minted once per unit of work — an edge batch at
ingest-enqueue (``QueueItem.from_arrays``) or a query at server accept —
and rides the existing plumbing: ``QueueItem.trace_id`` through queues
and spills, a new field on the wire codec's ``item`` frames (version 2),
and span-event lists inside publish/metrics beats coming back up.

Each process keeps one bounded ring (``get_trace_log()``).  Remote
workers ``drain()`` their ring into the beats they already send; the
parent ``absorb()``s, so one batch's enqueue -> dispatch -> publish ->
adopt chain (or a query's accept -> plan -> execute -> reply chain) is
reconstructable from a single JSONL dump regardless of transport.

Those events carry wall-clock ``ts`` because they cross processes.  Host
phases inside one process are timed with ``TraceLog.span`` instead: a
context manager that enters ``jax.profiler.TraceAnnotation`` (so the span
lands in any profiler trace, on the device trace's clock) and, on exit,
appends ``Span(name, t0_ns, t1_ns, key, thread)`` on
``time.perf_counter_ns`` to a second bounded ring.  ``key`` ties together
the spans of one unit of work: a worker's dispatch sequence number, or the
epoch a publish produces.  Span names are ``kmatrix.<layer>.<phase>``.
"""
from __future__ import annotations

import json
import os
import threading
import time
from collections import deque
from typing import Any, NamedTuple

__all__ = ["new_trace_id", "Span", "TraceLog", "get_trace_log",
           "reset_trace_log"]

DEFAULT_CAPACITY = 4096
# Holds a 51 s window at about 2,000 spans/s, the rate of a saturated
# ingest worker (about 7 spans per dispatch).
SPAN_CAPACITY = 1 << 17


class Span(NamedTuple):
    name: str
    t0_ns: int  # time.perf_counter_ns at entry
    t1_ns: int  # ... and at exit
    key: Any  # what ties spans of one unit of work together, or None
    thread: str


class _SpanScope:
    """One live span: the profiler annotation plus the ring record."""

    __slots__ = ("_log", "_name", "_key", "_ann", "_t0")

    def __init__(self, log: "TraceLog", name: str, key: Any) -> None:
        self._log, self._name, self._key = log, name, key

    def __enter__(self) -> "_SpanScope":
        from jax.profiler import TraceAnnotation

        self._ann = TraceAnnotation(self._name)
        self._ann.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        t1 = time.perf_counter_ns()
        self._ann.__exit__(*exc)
        self._log.record_span(self._name, self._t0, t1, self._key)


def new_trace_id() -> str:
    return os.urandom(8).hex()


class TraceLog:
    """Thread-safe bounded ring of span events."""

    def __init__(self, capacity: int = DEFAULT_CAPACITY) -> None:
        self._events: deque[dict] = deque(maxlen=capacity)
        self._lock = threading.Lock()
        self._emitted = 0
        self._spans: deque[Span] = deque(maxlen=SPAN_CAPACITY)
        self._spans_dropped = 0

    def emit(self, trace_id: str, span: str, event: str,
             **attrs: Any) -> None:
        if not trace_id:
            return
        rec = {"ts": time.time(), "trace": trace_id, "span": span,
               "event": event}
        if attrs:
            rec.update(attrs)
        with self._lock:
            self._events.append(rec)
            self._emitted += 1

    def span(self, name: str, key: Any = None):
        """Context manager timing one host phase (see the module doc)."""
        return _SpanScope(self, name, key)

    def record_span(self, name: str, t0_ns: int, t1_ns: int,
                    key: Any = None, thread: str | None = None) -> None:
        """Append one finished span; the oldest is dropped, and counted,
        once the ring is full."""
        if thread is None:
            thread = threading.current_thread().name
        rec = Span(name, t0_ns, t1_ns, key, thread)
        with self._lock:
            if len(self._spans) == self._spans.maxlen:
                self._spans_dropped += 1
            self._spans.append(rec)

    def spans(self) -> list[Span]:
        """Every span in the ring, in the order they ended."""
        with self._lock:
            return list(self._spans)

    @property
    def spans_dropped(self) -> int:
        """Spans the full ring has dropped since it was made or cleared."""
        return self._spans_dropped

    def absorb(self, events) -> None:
        """Fold a batch of remote events (from a drained child ring)."""
        if not events:
            return
        with self._lock:
            for rec in events:
                if isinstance(rec, dict) and rec.get("trace"):
                    self._events.append(rec)
                    self._emitted += 1

    def drain(self) -> list[dict]:
        """Remove and return everything buffered (child -> beat path)."""
        with self._lock:
            out = list(self._events)
            self._events.clear()
            return out

    def events(self, trace_id: str | None = None) -> list[dict]:
        with self._lock:
            evs = list(self._events)
        if trace_id is None:
            return evs
        return [e for e in evs if e["trace"] == trace_id]

    def chain(self, trace_id: str) -> list[str]:
        """The ordered event names seen for one trace."""
        return [e["event"] for e in self.events(trace_id)]

    def dump_jsonl(self, path: str) -> int:
        """Append-write current events, then spans, as JSONL; returns
        lines written.  A span line has ``t0_ns``/``t1_ns`` and no
        ``trace``."""
        evs = self.events()
        spans = self.spans()
        with open(path, "a") as fh:
            for rec in evs:
                fh.write(json.dumps(rec, default=str) + "\n")
            for sp in spans:
                fh.write(json.dumps(sp._asdict(), default=str) + "\n")
        return len(evs) + len(spans)

    @property
    def emitted(self) -> int:
        return self._emitted

    def clear(self) -> None:
        with self._lock:
            self._events.clear()
            self._spans.clear()
            self._spans_dropped = 0


_GLOBAL: TraceLog | None = None
_GLOBAL_LOCK = threading.Lock()


def get_trace_log() -> TraceLog:
    global _GLOBAL
    with _GLOBAL_LOCK:
        if _GLOBAL is None:
            _GLOBAL = TraceLog()
        return _GLOBAL


def reset_trace_log() -> TraceLog:
    global _GLOBAL
    with _GLOBAL_LOCK:
        _GLOBAL = TraceLog()
        return _GLOBAL
