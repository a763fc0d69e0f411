"""The benchmark's arithmetic: percentiles, rates, freshness, roofline shares,
and the table of device peaks (``bench/peaks.json``)."""
from __future__ import annotations

import bisect
import json
from pathlib import Path

import numpy as np

PEAKS_FILE = Path(__file__).resolve().parents[1] / "peaks.json"


def percentile(values, q: float) -> float:
    """The ``q``-th percentile (0-100) by linear interpolation between
    closest ranks; NaN when there are no values."""
    v = np.asarray(values, np.float64)
    if v.size == 0:
        return float("nan")
    return float(np.percentile(v, q))


def visible_edges(publishes, t: float) -> int:
    """Edges queryable at host time ``t``: ``n_edges`` of the newest
    snapshot published at or before ``t`` (publishes sorted by time)."""
    times = [p[0] for p in publishes]
    i = bisect.bisect_right(times, t)
    return int(publishes[i - 1][1]) if i else 0


def rate_over(publishes, t0: float, t1: float) -> float:
    """Edges made queryable per second over ``[t0, t1]``."""
    return (visible_edges(publishes, t1) - visible_edges(publishes, t0)) \
        / (t1 - t0)


def visibility_times(publishes, cumulative_edges) -> list:
    """For each cumulative edge count, the host time of the first publish
    whose ``n_edges`` covers it (None if none did)."""
    counts = [p[1] for p in publishes]
    out = []
    for e in cumulative_edges:
        i = bisect.bisect_left(counts, e)
        out.append(publishes[i][0] if i < len(publishes) else None)
    return out


def load_peaks(device_kind: str, path: Path = PEAKS_FILE) -> dict:
    """The peaks of ``device_kind``; an unknown device is an error."""
    table = json.loads(path.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks recorded for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def ingest_update_bytes(raw_rows: int, distinct_rows: int,
                        depth: int) -> int:
    """Bytes the kMatrix update rule needs for one client batch: read each
    raw (src, dst, weight) row of int32 once, and read-modify-write one pool
    cell and one conn cell per layer, int32 each, for every distinct
    (src, dst) of the batch."""
    return 12 * int(raw_rows) + 2 * 2 * 4 * int(depth) * int(distinct_rows)


def roofline_share(least_time_s: float, measured_s: float) -> float | None:
    """Least time over measured time, in percent; None without a time."""
    if measured_s <= 0:
        return None
    return 100.0 * least_time_s / measured_s


def distinct_pairs(src: np.ndarray, dst: np.ndarray) -> int:
    key = (src.astype(np.int64) << 32) | (dst.astype(np.int64) & 0xFFFFFFFF)
    return int(np.unique(key).size)
