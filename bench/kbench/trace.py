"""Reduction of a profiler trace to device busy time, idle gaps and the time
of named device work.

A trace is read with ``jax.profiler.ProfileData`` into plain ``Event``
tuples: the device planes' events (``/device:TPU:n``) and the benchmark's own
host annotations (``bench.*``), all on the profiler's one clock.  The traced
window is the span of the ``bench.window`` annotation.  Per-layer metrics
pick device events by name patterns kept in their own files; nothing here
knows a kernel's name.
"""
from __future__ import annotations

import dataclasses
import glob
import json
import os
import re

WINDOW = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float  # ns on the profiler clock
    end: float
    line: str = ""
    plane: str = ""


@dataclasses.dataclass
class TraceData:
    device: list  # Event on device planes
    host: list  # Event of the benchmark's host annotations
    t0: float
    t1: float
    n_devices: int = 1

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) / 1e9

    def line(self, name: str) -> list:
        """Device events of one line kind (``XLA Ops``, ``XLA Modules``)."""
        return [e for e in self.device if e.line == name]

    def busy_s(self) -> float:
        """Seconds in which some operation ran, averaged over the devices."""
        ops = self.line(OPS_LINE) or self.device
        per_plane: dict = {}
        for e in ops:
            per_plane.setdefault(e.plane, []).append((e.start, e.end))
        total = sum(union_ns(iv, self.t0, self.t1) for iv in per_plane.values())
        return total / max(self.n_devices, 1) / 1e9

    def to_json(self) -> str:
        return json.dumps({"t0": self.t0, "t1": self.t1,
                           "n_devices": self.n_devices,
                           "device": [dataclasses.astuple(e)
                                      for e in self.device],
                           "host": [dataclasses.astuple(e)
                                    for e in self.host]})

    @staticmethod
    def from_json(text: str) -> "TraceData":
        d = json.loads(text)
        return TraceData([Event(*e) for e in d["device"]],
                         [Event(*e) for e in d["host"]],
                         d["t0"], d["t1"], d.get("n_devices", 1))


def load(trace_dir: str) -> TraceData:
    """Read the newest ``*.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no xplane trace under {trace_dir}")
    data = ProfileData.from_file(max(files, key=os.path.getmtime))
    device, host = [], []
    chips = set()  # planes that run XLA ops; not e.g. a "/device:CUSTOM" one
    for plane in data.planes:
        is_device = plane.name.startswith("/device:")
        for line in plane.lines:
            for e in line.events:
                if is_device:
                    if line.name == OPS_LINE:
                        chips.add(plane.name)
                    device.append(Event(e.name, e.start_ns, e.end_ns,
                                        line.name, plane.name))
                elif e.name.startswith("bench."):
                    host.append(Event(e.name, e.start_ns, e.end_ns,
                                      line.name, plane.name))
    windows = [e for e in host if e.name == WINDOW]
    if not windows:
        raise ValueError("the trace holds no bench.window annotation")
    w = windows[0]
    return TraceData(device, host, w.start, w.end, max(len(chips), 1))


def union_ns(intervals, t0: float, t1: float) -> float:
    """Length of the union of ``[start, end)`` intervals clipped to
    ``[t0, t1]``."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted((max(s, t0), min(e, t1)) for s, e in intervals):
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def idle_gaps(intervals, t0: float, t1: float) -> list:
    """The ``(start, end)`` stretches of ``[t0, t1]`` no interval covers."""
    gaps, cursor = [], t0
    for s, e in sorted((max(s, t0), min(e, t1)) for s, e in intervals):
        if e <= s:
            continue
        if s > cursor:
            gaps.append((cursor, s))
        cursor = max(cursor, e)
    if cursor < t1:
        gaps.append((cursor, t1))
    return gaps


def matching(events, patterns) -> list:
    """Events whose name matches any of the regular expressions."""
    rx = [re.compile(p) for p in patterns]
    return [e for e in events if any(r.search(e.name) for r in rx)]


def in_window(events, t0: float, t1: float) -> list:
    """Events that start inside ``[t0, t1)``."""
    return [e for e in events if t0 <= e.start < t1]


def host_label(gap, host_events) -> str:
    """What the benchmark's host threads were doing during a gap: the
    annotations that overlap it, most overlap first."""
    a, b = gap
    overlap: dict = {}
    for e in host_events:
        if e.name == WINDOW:
            continue
        o = min(b, e.end) - max(a, e.start)
        if o > 0:
            overlap[e.name] = overlap.get(e.name, 0.0) + o
    if not overlap:
        return "no annotation"
    return "+".join(sorted(overlap, key=lambda k: -overlap[k]))


def _op_kind(name: str) -> str:
    """An op's kind and result shape from its HLO text
    (``%fusion.16 = s32[117440512]{0} fusion(...)`` -> ``fusion s32[117440512]``),
    or the name without its numeric instance suffix."""
    head, _, rest = name.partition(" = ")
    kind = re.sub(r"[.\-_]\d+$", "", head.lstrip("%"))
    shape = re.match(r"[^\s{]*", rest).group(0) if rest else ""
    return f"{kind} {shape}".strip()


def breakdown(trace: TraceData, top: int = 10) -> dict:
    """The device operations that took most time and the longest idle gaps
    labelled by the host annotations that overlap them."""
    ops = in_window(trace.line(OPS_LINE) or trace.device, trace.t0, trace.t1)
    totals: dict = {}
    for e in ops:
        k = _op_kind(e.name)
        totals[k] = totals.get(k, 0.0) + (e.end - e.start)
    device_ops = sorted(totals.items(), key=lambda kv: -kv[1])[:top]
    gaps = idle_gaps([(e.start, e.end) for e in ops], trace.t0, trace.t1)
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    return {
        "device_ops": [[k, v / 1e9] for k, v in device_ops],
        "idle_gaps": [[host_label(g, trace.host), (g[1] - g[0]) / 1e9]
                      for g in gaps],
    }
