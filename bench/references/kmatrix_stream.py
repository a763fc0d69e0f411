"""Plain reference of the kMatrix sketch for configurations whose lap is a
long stream (tens of millions of edges).

Everything but ``counters_after`` is ``references/kmatrix.py``'s, taken
as it is: the layout derived from the configuration (sample, banded plan,
hashing), the mismatch count and the direct query answers.

``kmatrix.counters_after`` addresses every edge of the whole lap at once
before it masks the submitted prefix.  At a 63.5M-edge lap that is several
int64 arrays of [7, 63.5M] (about 3.5 GB each) and a ``np.unique`` over
about 440M entries, though a 10 s window submits about a tenth of the lap.
This one visits only the submitted prefix, at most ``CHUNK`` edges at a
time, and adds each block's counts into a dense int64 array of the block's
cells (the largest block at the 150 MiB budget has 7 x 1024^2 cells).
"""
from __future__ import annotations

from pathlib import Path

import numpy as np

from kbench import discover

_base = discover.reference(Path(__file__).resolve().parents[1], "kmatrix")

Layout = _base.Layout
count_mismatches = _base.count_mismatches
Answers = _base.Answers
same_answer = _base.same_answer

CHUNK = 1 << 22


def counters_after(layout: Layout, lap, n_edges: int,
                   chunk: int = CHUNK) -> dict:
    """The counters after the first ``n_edges`` submitted edges, sparse:
    per block, sorted cell indices and their int64 values (every other cell
    is zero).  The prefix is ``laps`` whole laps and the lap's first
    ``rem`` edges, so lap edge ``i`` counts ``laps + 1`` times below
    ``rem`` and ``laps`` times above it."""
    laps, rem = lap.prefix(n_edges)
    acc = {name: np.zeros(int(np.prod(shape)), np.int64)
           for name, shape in layout.blocks.items()}
    for lo in range(0, lap.length if laps else rem, chunk):
        hi = min(lo + chunk, lap.length if laps else rem)
        times = np.where(np.arange(lo, hi) < rem, laps + 1, laps)
        w = lap.weight[lo:hi].astype(np.int64) * times
        for name, (cell, edge) in _base._cells(
                layout, lap.src[lo:hi], lap.dst[lo:hi]).items():
            counts = np.bincount(cell, weights=w[edge],
                                 minlength=acc[name].size)
            acc[name] += np.rint(counts).astype(np.int64)
    out = {}
    for name, counts in acc.items():
        idx = np.flatnonzero(counts)
        out[name] = (idx, counts[idx])
    return out
