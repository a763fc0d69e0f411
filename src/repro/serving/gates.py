"""Shared hard-gate helpers: conservation + exactness checks for serving.

One implementation for every drive path that gates on correctness —
``launch/query_serve.py``, ``serving/sharding.py``, ``chip_smoke.py`` and
the test suite.  Everything here is pure checking:
no timing, no I/O, no policy.

The two invariant families (DESIGN.md §Runtime / §Sharding):

  conservation   after a graceful drain, published counter mass + accounted
                 drops == stream total, per worker and summed;
  exactness      engine answers == direct module-level answers, and a
                 (merged) sketch is bit-identical — counters AND estimates —
                 to a single-sketch replay of the same stream.
"""
from __future__ import annotations

import numpy as np
import jax

from repro.serving import engine as eng
from repro.serving.snapshot import Snapshot


def values_match(a, b) -> bool:
    """Equality for query answers (heavy-nodes answers are array pairs)."""
    if isinstance(a, tuple):
        return (np.array_equal(a[0], b[0]) and np.array_equal(a[1], b[1]))
    return a == b


def mismatched_indices(got: list, want: list) -> list[int]:
    """Indices where engine answers diverge from oracle answers."""
    return [i for i, (g, w) in enumerate(zip(got, want))
            if not values_match(g, w)]


def layout_counters_equal(a, b) -> bool:
    """Bit-equality of a sketch's counter state (pool(s) + conn), layout
    aware; the ``overflow`` diagnostic is deliberately excluded — dispatch
    capacity differs between sub-batch shapes, so sharded and unsharded
    runs legitimately tally different fallback volumes for identical
    counters."""
    if hasattr(a, "pools"):
        return (all(np.array_equal(np.asarray(x), np.asarray(y))
                    for x, y in zip(a.pools, b.pools))
                and np.array_equal(np.asarray(a.conn), np.asarray(b.conn)))
    if hasattr(a, "pool"):
        return (np.array_equal(np.asarray(a.pool), np.asarray(b.pool))
                and np.array_equal(np.asarray(a.conn), np.asarray(b.conn)))
    if hasattr(a, "table"):
        return np.array_equal(np.asarray(a.table), np.asarray(b.table))
    return np.array_equal(np.asarray(a.counters), np.asarray(b.counters))


def replay_sketch(mod, template, stream, n_batches: int):
    """Single-sketch oracle: ingest stream batches ``[0, n_batches)`` into
    ``template`` (usually an ``empty_like`` clone sharing the layout under
    test) through the module's jitted ingest."""
    ing = jax.jit(mod.ingest)
    sk = template
    for i in range(n_batches):
        sk = ing(sk, stream.batch(i))
    return sk


def replay_exactness(snapshot: Snapshot, replay, requests,
                     *, answers=None) -> dict:
    """Gate a snapshot against a replayed sketch: bit-identical counters
    AND bit-identical direct estimates for ``requests``.

    ``replay`` must share the snapshot sketch's layout.  ``answers`` lets a
    caller reuse direct answers it already computed for the snapshot (the
    per-request oracle is the slow half of the gate).  Returns the
    ``counters_equal`` / ``estimates_equal`` / ``ok`` verdict dict every
    serve-bench record embeds.
    """
    counters_equal = layout_counters_equal(snapshot.sketch, replay)
    replay_snap = Snapshot(snapshot.tenant_id + "/replay", snapshot.epoch,
                           replay, snapshot.kind, snapshot.n_edges)
    if answers is None:
        answers = eng.direct_answers(snapshot, requests)
    replay_answers = eng.direct_answers(replay_snap, requests)
    estimates_equal = all(values_match(a, b)
                          for a, b in zip(answers, replay_answers))
    return {
        "counters_equal": bool(counters_equal),
        "estimates_equal": bool(estimates_equal),
        "ok": bool(counters_equal and estimates_equal),
    }


def conservation_verdict(published: int, dropped: int, stream_total: int,
                         unaccounted) -> dict:
    """Edge-mass verdict shared by the single-tenant and sharded gates:
    published + accounted drops must equal the stream total AND every
    worker must individually balance (``unaccounted`` is one int or a
    per-worker list)."""
    per_worker = (list(unaccounted) if hasattr(unaccounted, "__len__")
                  else [unaccounted])
    return {
        "published_edges": published,
        "dropped_edges": dropped,
        "stream_total_edges": stream_total,
        "unaccounted_edges": sum(per_worker),
        "conservation_ok": bool(
            published + dropped == stream_total
            and all(u == 0 for u in per_worker)),
    }
