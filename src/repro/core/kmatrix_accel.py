"""kMatrix width-class backend — the TPU-native layout as a full sketch.

``KMatrixAccel`` stores the same counters as the flat-pool ``KMatrix``
(``repro.core.kmatrix``) in a different physical arrangement: partition
widths are quantized to power-of-two *width classes*, and every partition of
width ``w_c`` lives as one row of a rectangular pool int32[d, P_c, w_c, w_c].
Rectangular pools are what makes ingest MXU-shaped — batches become
per-class one-hot matmuls (``repro.kernels.matrix_ingest``) instead of a
serialized XLA scatter.

This module is the *sketch protocol* surface the production layers consume
(serving registry/snapshots, runtime workers, checkpoints, benchmarks):
``create / ingest / edge_freq / node_out_freq / conn_cells / empty_like /
merge`` — mirror-compatible with ``repro.core.kmatrix`` so every layer above
is layout-agnostic.  Only ``ingest`` touches Pallas (lazily, via
``repro.kernels.ops``); queries and merges are pure jnp, so importing this
module never requires a TPU.

Layout equivalence: the class layout and the flat layout index the *same*
cells — cell ``(hi, hj)`` of partition ``p`` is ``pools[class(p)][d,
index(p), hi, hj]`` here and ``pool[d, offset(p) + hi*w_p + hj]`` there.
``to_flat_layout`` / ``to_class_layout`` apply that permutation bit-exactly,
so checkpoints written under either backend load into the other and
``benchmarks/serve_bench.py`` can hard-gate estimate equality.

Backend selection (``sketch_backend``): explicit arg > $REPRO_SKETCH_BACKEND
> platform default — ``pallas`` on TPU, ``flat`` elsewhere (the pallas path
still *runs* off-TPU via interpret mode; it is just slower than XLA's fused
scatter, so it is opt-in there).
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.common.hashing import HashFamily, families_match, fastrange
from repro.common.struct import pytree_dataclass, static_field
from repro.core.kmatrix import KMatrix
from repro.core.partitioning import good_turing_outlier_share, plan_for
from repro.core.routing import RouteTable, routes_match
from repro.core.types import EdgeBatch, VertexStats


def sketch_backend(backend: str | None = None) -> str:
    """Resolve the kMatrix sketch backend: explicit arg >
    $REPRO_SKETCH_BACKEND > platform default (width-class Pallas layout on
    TPU, flat-pool XLA scatter elsewhere)."""
    backend = backend or os.environ.get("REPRO_SKETCH_BACKEND") or (
        "pallas" if jax.default_backend() == "tpu" else "flat")
    if backend not in ("flat", "pallas"):
        raise ValueError(f"unknown sketch backend {backend!r} "
                         "(expected 'flat' or 'pallas')")
    return backend


# Alias-safe under buffer donation (serving/snapshot.py): ingest (including
# the lazily-dispatched Pallas path) / merge / empty_like never retain a
# reference to an input leaf, so the sketch may sit in a donate_argnums
# position.  empty_like reuses hash/route leaves by reference — donating
# callers must deep-copy first (SnapshotBuffer._private_copy does).
DONATION_SAFE = True


@pytree_dataclass
class KMatrixAccel:
    """kMatrix with power-of-two width classes (TPU-native layout).

    ``pools[c]`` holds every partition of width ``class_widths[c]`` as one
    rectangular array int32[d, P_c, w_c, w_c].  ``part_class``/``part_index``
    map a global partition id to (class, row-within-class).  ``overflow``
    counts ingest updates that exceeded the per-partition dispatch capacity
    and took the exact scatter fallback — a *diagnostic* (capacity
    regressions show up as throughput cliffs), never a correctness term: the
    fallback counts those edges exactly.
    """

    pools: tuple  # tuple[int32[d, P_c, w_c, w_c], ...]
    conn: jax.Array  # int32[d, cw, cw]
    overflow: jax.Array  # int32[] scatter-fallback updates (diagnostic)
    hashes: HashFamily
    route: RouteTable  # offsets/widths are the flat-twin layout (see create)
    part_class: jax.Array  # int32[P]
    part_index: jax.Array  # int32[P]
    part_width: jax.Array  # int32[P]
    class_widths: tuple = static_field()
    class_counts: tuple = static_field()
    conn_w: int = static_field()
    # Expected per-partition share of stream edges, from the partition
    # plan's banded load (sampled frequency mass per partition, Good-Turing
    # share for the outlier).  Sizes the ingest dispatch capacity
    # (``dispatch_capacity``) from the plan instead of a uniform 2B/P.
    # None on sketches relayouted from a flat pool (no sample available):
    # those fall back to the uniform formula.
    load_shares: tuple | None = static_field(default=None)

    @property
    def depth(self) -> int:
        return self.conn.shape[0] if self.conn.ndim == 3 else self.pools[0].shape[0]

    @property
    def num_counters(self) -> int:
        return sum(int(p.size) for p in self.pools) + int(self.conn.size)

    @staticmethod
    def create(
        *,
        bytes_budget: int,
        stats: VertexStats,
        depth: int = 7,
        seed: int = 0,
        partitioner: str = "auto",  # same default as KMatrix.create: a
        # backend switch must never change which plan a config produces
        n_bands: int = 16,
        max_partitions: int = 64,
        min_width: int = 8,
        conn_frac: float = 0.1,
        outlier_frac: float | None = None,
    ) -> "KMatrixAccel":
        counters = bytes_budget // 4
        per_layer = max(counters // depth, 4)
        conn_w = int(np.sqrt(per_layer * conn_frac)) if conn_frac > 0 else 0
        total_width = max(int(np.sqrt(per_layer - conn_w * conn_w)), 2)
        plan = plan_for(
            partitioner, stats, total_width, square=True, n_bands=n_bands,
            max_partitions=max_partitions, min_width=min_width,
            outlier_frac=outlier_frac,
        )
        # Quantize each width DOWN to a power of two (keeps the budget).
        widths = np.asarray([1 << (int(p.width).bit_length() - 1)
                             for p in plan.partitions], dtype=np.int32)
        part_class, part_index, classes, counts = _class_structure(widths)
        _check_class_widths(classes)
        # offsets are the FLAT layout invariant (cumsum of w_p^2 slabs) even
        # though the class layout never reads them: one route table must
        # serve both layouts, or to_flat_layout / checkpoint interchange
        # would silently mis-place slabs.
        slab = widths.astype(np.int64) ** 2
        offsets = np.concatenate([[0], np.cumsum(slab)[:-1]]).astype(np.int32)
        route = RouteTable(
            keys=jnp.asarray(plan.route_keys),
            part=jnp.asarray(plan.route_part),
            offsets=jnp.asarray(offsets),
            widths=jnp.asarray(widths),
            outlier=plan.outlier,
            n_partitions=len(widths),
            max_width=int(widths.max()),
        )
        pools = tuple(
            jnp.zeros((depth, counts[c], classes[c], classes[c]), jnp.int32)
            for c in range(len(classes))
        )
        load_shares = _plan_load_shares(plan, stats)
        return KMatrixAccel(
            pools=pools,
            conn=jnp.zeros((depth, conn_w, conn_w), jnp.int32),
            overflow=jnp.zeros((), jnp.int32),
            hashes=HashFamily.create(seed, depth),
            route=route,
            part_class=jnp.asarray(part_class),
            part_index=jnp.asarray(part_index),
            part_width=jnp.asarray(widths),
            class_widths=tuple(classes),
            class_counts=tuple(counts),
            conn_w=conn_w,
            load_shares=load_shares,
        )


def _plan_load_shares(plan, stats: VertexStats) -> tuple:
    """Expected stream-edge share per partition, from the sample.

    Sampled partitions split the SEEN share of the stream proportionally to
    their sampled frequency mass; the outlier partition's share is the
    Good-Turing estimate of unseen-source traffic (the same estimate that
    sized its width).  Shares sum to ~1 and are static Python floats, so the
    ingest capacity derived from them stays a trace-time constant.
    """
    vert = np.asarray(stats.vertex)  # sorted unique (types.py contract)
    freq = np.asarray(stats.freq, np.float64)
    total = max(float(freq.sum()), 1e-9)
    unseen = good_turing_outlier_share(freq)
    shares = []
    for p in plan.partitions[:-1]:
        pos = np.searchsorted(vert, np.asarray(p.vertices))
        shares.append(float(freq[pos].sum()) / total * (1.0 - unseen))
    shares.append(float(unseen))  # outlier partition (appended last)
    return tuple(round(s, 6) for s in shares)


def dispatch_capacity(sk: KMatrixAccel, batch_size: int,
                      block_b: int = 128) -> int:
    """Per-partition ingest dispatch capacity for one batch of ``batch_size``.

    Sized from the partition plan's banded load: the hottest partition's
    expected share of the stream (``load_shares``) with 2x headroom, capped
    at the batch size (a partition can never receive more than B edges, and
    capacity == B guarantees a zero overflow tail).  The legacy uniform
    ``2B/P`` is kept only as the fallback for relayouted sketches that carry
    no sample — on skewed streams it undersizes the hot partition by the
    skew factor and every excess edge pays the scatter-fallback path
    (ROADMAP dispatch-capacity item; regression visible as
    ``overflow_edges`` in runtime metrics / serve_bench / BENCH_ingest).
    Rounded up to the Pallas ingest block so the kernel grid stays aligned.
    """
    if sk.load_shares:
        cap = int(np.ceil(2.0 * max(sk.load_shares) * batch_size))
        cap = min(cap, batch_size)
    else:
        cap = (2 * batch_size) // max(sk.route.n_partitions, 1)
    cap = max(cap, min(block_b, batch_size))
    return -(-cap // block_b) * block_b


def _class_structure(widths: np.ndarray):
    """Group partition widths into sorted classes.

    Returns (part_class, part_index, class_widths, class_counts) with the
    deterministic convention shared by ``create`` and ``to_class_layout``:
    classes ascend by width; within a class, rows follow global partition
    order.
    """
    classes = sorted(set(int(w) for w in widths))
    part_class = np.asarray([classes.index(int(w)) for w in widths], np.int32)
    part_index = np.zeros(len(widths), np.int32)
    counts = []
    for c in range(len(classes)):
        members = np.nonzero(part_class == c)[0]
        part_index[members] = np.arange(len(members))
        counts.append(len(members))
    return part_class, part_index, classes, counts


def _check_class_widths(classes) -> None:
    """Refuse a layout whose widest class the ingest kernel cannot compile."""
    from repro.kernels.matrix_ingest import MAX_WIDTH

    if max(classes) > MAX_WIDTH:
        raise ValueError(
            f"width class {max(classes)} exceeds the widest class the Pallas "
            f"ingest kernel is built for ({MAX_WIDTH}); use the flat sketch "
            "backend, a smaller budget or more partitions")


# --------------------------------------------------------------- protocol --

def ingest(sk: KMatrixAccel, batch: EdgeBatch, *,
           capacity: int | None = None, block_b: int = 128) -> KMatrixAccel:
    """Exact batched ingest via the per-class Pallas MXU kernel.

    Thin protocol wrapper; the kernel dispatch lives in
    ``repro.kernels.ops.kmatrix_accel_ingest`` (imported lazily so the pure
    query surface of this module never pulls in Pallas).
    """
    from repro.kernels.ops import kmatrix_accel_ingest

    return kmatrix_accel_ingest(sk, batch, capacity=capacity, block_b=block_b)


def edge_freq(sk: KMatrixAccel, src: jax.Array, dst: jax.Array) -> jax.Array:
    """Point queries on the class layout (pure gather; query volume is tiny
    next to ingest volume, so this path stays unfused)."""
    p = sk.route.lookup(src)
    w_p = sk.part_width[p]
    hi = fastrange(sk.hashes.mix(src), w_p)  # [d, *S]
    hj = fastrange(sk.hashes.mix(dst), w_p)
    d = sk.depth
    rows = jnp.arange(d, dtype=jnp.int32).reshape((d,) + (1,) * src.ndim)
    est = jnp.full(src.shape, jnp.iinfo(jnp.int32).max, jnp.int32)
    for c, (w_c, p_c) in enumerate(zip(sk.class_widths, sk.class_counts)):
        if p_c == 0:
            continue
        sel = sk.part_class[p] == c
        q = jnp.where(sel, sk.part_index[p], 0)
        vals = jnp.min(sk.pools[c][rows, q[None], hi, hj], axis=0)
        est = jnp.where(sel, vals, est)
    return est


def node_out_freq(sk: KMatrixAccel, v: jax.Array) -> jax.Array:
    """Row-sum of v's row inside its class block, min over layers.

    Bit-identical to ``kmatrix.node_out_freq`` on the flat twin: the class
    block row holds exactly the slab cells the flat masked gather sums.
    """
    p = sk.route.lookup(v)
    hi_full = sk.hashes.mix(v)  # [d, *S] uint32
    d = sk.depth
    rows = jnp.arange(d, dtype=jnp.int32).reshape((d,) + (1,) * v.ndim)
    est = jnp.full(v.shape, jnp.iinfo(jnp.int32).max, jnp.int32)
    for c, (w_c, p_c) in enumerate(zip(sk.class_widths, sk.class_counts)):
        if p_c == 0:
            continue
        sel = sk.part_class[p] == c
        q = jnp.where(sel, sk.part_index[p], 0)
        hi = fastrange(hi_full, w_c)  # [d, *S]
        vals = jnp.min(
            jnp.sum(sk.pools[c][rows, q[None], hi, :], axis=-1), axis=0)
        est = jnp.where(sel, vals, est)
    return est


def conn_cells(sk: KMatrixAccel, v: jax.Array) -> jax.Array:
    """Per-layer slot of vertex ``v`` in the global connectivity matrix."""
    return fastrange(sk.hashes.mix(v), sk.conn_w)


def empty_like(sk: KMatrixAccel) -> KMatrixAccel:
    """A zero-counter sketch sharing ``sk``'s layout, routing and hashes
    (snapshot hook, DESIGN.md §Serving — same contract as ``kmatrix``)."""
    return sk.replace(
        pools=tuple(jnp.zeros_like(p) for p in sk.pools),
        conn=jnp.zeros_like(sk.conn),
        overflow=jnp.zeros_like(sk.overflow),
    )


def merge(a: KMatrixAccel, b: KMatrixAccel) -> KMatrixAccel:
    """Counter-additivity over class pools (data-parallel ingest, serving
    snapshot publishes).  Same rejection rules as ``KMatrix.merge``: layouts
    can coincide across hash seeds or partition plans, so both are checked
    explicitly (outside jit) rather than trusted from shapes."""
    assert (a.class_widths == b.class_widths
            and a.class_counts == b.class_counts
            and a.conn_w == b.conn_w)
    if families_match(a.hashes, b.hashes) is False:
        raise ValueError(
            "merge: operands use different hash families (built with "
            "different seeds); merging them silently corrupts estimates")
    if routes_match(a.route, b.route) is False:
        raise ValueError(
            "merge: operands use different partition plans (built from "
            "different samples); edges route to different slabs, so summing "
            "the pools silently corrupts estimates")
    return a.replace(
        pools=tuple(pa + pb for pa, pb in zip(a.pools, b.pools)),
        conn=a.conn + b.conn,
        overflow=a.overflow + b.overflow,
    )


# ------------------------------------------------------------- relayout ----

def to_flat_layout(sk: KMatrixAccel) -> KMatrix:
    """Bit-exact relayout: class pools -> the flat-pool ``KMatrix`` twin.

    Pure permutation — cell ``(hi, hj)`` of partition ``p`` moves from
    ``pools[class(p)][:, index(p)]`` to ``pool[:, offset(p) + hi*w_p + hj]``.
    The route table (with its flat offsets), hashes, conn matrix AND the
    ``overflow`` diagnostic carry over unchanged, so every estimate of the
    result equals the source's and a relayout round-trip (or a checkpoint
    migration through the flat layout) preserves the scatter-fallback tally
    instead of zeroing it.
    """
    d = sk.depth
    widths = np.asarray(sk.part_width)
    offsets = np.asarray(sk.route.offsets)
    part_class = np.asarray(sk.part_class)
    part_index = np.asarray(sk.part_index)
    pool_size = int((widths.astype(np.int64) ** 2).sum())
    pool = jnp.zeros((d, pool_size), jnp.int32)
    for p in range(sk.route.n_partitions):
        w = int(widths[p])
        block = sk.pools[int(part_class[p])][:, int(part_index[p])]
        pool = jax.lax.dynamic_update_slice(
            pool, block.reshape(d, w * w), (0, int(offsets[p])))
    return KMatrix(
        pool=pool,
        conn=sk.conn,
        overflow=sk.overflow,
        hashes=sk.hashes,
        route=sk.route,
        pool_size=pool_size,
        conn_w=sk.conn_w,
    )


def to_class_layout(sk: KMatrix, *, overflow: jax.Array | int | None = None
                    ) -> KMatrixAccel:
    """Bit-exact relayout: flat pool -> width-class pools (inverse of
    ``to_flat_layout``).

    Requires the flat sketch to be a *class-layout twin*: every partition
    width a power of two and offsets the standard ``cumsum(w^2)`` slabs —
    i.e. a sketch built by either backend's ``create`` (or a checkpoint of
    one), not an arbitrary un-quantized plan.  The scatter-fallback tally
    defaults to the flat sketch's own ``overflow`` leaf (which
    ``to_flat_layout`` preserves), so a round-trip is identity on the
    diagnostic too; pass ``overflow`` explicitly only to override it.
    """
    widths = np.asarray(sk.route.widths)
    if len(widths) == 0:
        raise ValueError("to_class_layout: empty partition plan")
    if np.any((widths & (widths - 1)) != 0) or np.any(widths < 1):
        raise ValueError(
            f"to_class_layout: widths {widths.tolist()} are not all powers "
            "of two — this flat sketch was not built from a width-class "
            "plan; rebuild it under the pallas backend instead of relaying")
    slab = widths.astype(np.int64) ** 2
    expect_off = np.concatenate([[0], np.cumsum(slab)[:-1]])
    if not np.array_equal(np.asarray(sk.route.offsets), expect_off):
        raise ValueError(
            "to_class_layout: route offsets are not the standard cumsum "
            "slab layout; refusing a lossy relayout")
    part_class, part_index, classes, counts = _class_structure(widths)
    _check_class_widths(classes)
    d = sk.depth
    pools = []
    for c, w_c in enumerate(classes):
        members = np.nonzero(part_class == c)[0]
        blocks = [
            jax.lax.dynamic_slice(
                sk.pool, (0, int(expect_off[p])), (d, w_c * w_c)
            ).reshape(d, w_c, w_c)
            for p in members
        ]
        pools.append(jnp.stack(blocks, axis=1))
    if overflow is None:
        overflow = sk.overflow
    return KMatrixAccel(
        pools=tuple(pools),
        conn=sk.conn,
        overflow=jnp.asarray(overflow, jnp.int32).reshape(()),
        hashes=sk.hashes,
        route=sk.route,
        part_class=jnp.asarray(part_class),
        part_index=jnp.asarray(part_index),
        part_width=jnp.asarray(widths.astype(np.int32)),
        class_widths=tuple(classes),
        class_counts=tuple(counts),
        conn_w=sk.conn_w,
    )
