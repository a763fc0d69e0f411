"""Share of the window's device busy time spent in the Pallas one-hot
ingest kernel, in %: the union of the ``matrix_ingest`` op intervals over
the union of all op intervals, per device, summed over the devices.

The traced run also logs the kernel's time by width class, parsed from the
op's result shape: ``s32[d,P_c,w_c,w_c]`` in the trace's HLO text, or
``s32_d_P_c_w_c_w_c_`` in a breakdown's op kind."""
import re

from kbench import trace as tracing
from kbench.drive import log

PATTERN = r"^%?matrix_ingest(?=[._\s\-]|$)"
# d, P_c, w_c, w_c of the result, in either spelling
SHAPE = re.compile(r"s32[\[_](\d+)[,_](\d+)[,_](\d+)[,_](\d+)")


def width_class(name: str) -> int | None:
    """The width class an ingest op updates, from its result shape."""
    m = SHAPE.search(name)
    return int(m.group(3)) if m else None


def by_class_s(tr) -> dict:
    """Seconds of ``matrix_ingest`` ops inside the window by width class,
    averaged over the devices."""
    out: dict = {}
    for e in tracing.matching(tr.line(tracing.OPS_LINE) or tr.device,
                              [PATTERN]):
        s = min(e.end, tr.t1) - max(e.start, tr.t0)
        if s > 0:
            w = width_class(e.name)
            out[w] = out.get(w, 0.0) + s / 1e9
    n = max(tr.n_devices, 1)
    return {w: s / n for w, s in out.items()}


def read(ctx):
    tr = ctx.trace
    if tr is None or tr.window_s <= 0:
        return None
    ops = tr.line(tracing.OPS_LINE) or tr.device
    busy, kernel = 0.0, 0.0
    per_plane: dict = {}
    for e in ops:
        per_plane.setdefault(e.plane, []).append(e)
    for events in per_plane.values():
        busy += tracing.union_ns([(e.start, e.end) for e in events],
                                 tr.t0, tr.t1)
        kernel += tracing.union_ns(
            [(e.start, e.end) for e in tracing.matching(events, [PATTERN])],
            tr.t0, tr.t1)
    if busy <= 0 or kernel <= 0:
        return None
    log(f"matrix_ingest seconds by width class: {by_class_s(tr)}")
    return 100.0 * kernel / busy
