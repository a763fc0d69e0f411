"""jit'd public wrappers around the Pallas kernels.

Two consumption levels:

  * Global matrix sketches (TCM/gMatrix): drop-in accelerated ingest/lookup
    (`accel_matrix_ingest` / `accel_matrix_edge_freq`) on the (d, w, w)
    table — P=1 instances of the kernels.

  * kMatrix: the TPU-native `KMatrixAccel` state. Partition widths are
    quantized to power-of-two *width classes* so the pool rectangularizes
    into one (d, P_c, w_c, w_c) array per class — every block static, no
    scalar-prefetch offsets, and ingest batches become per-class MXU
    matmuls.  Edges are bucketed to (partition, slot) rectangles with a
    capacity factor; a sketch must count EVERY edge, so capacity overflow
    falls back to an exact in-jit scatter (never drops, unlike MoE).

Interpret mode is decided per call (``interpret_mode``): kernels compile
through Mosaic when JAX's default backend is a TPU and run in the Pallas
interpreter (same dataflow, Python-executed kernel body) anywhere else.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.common.hashing import fastrange
from repro.core.kmatrix_accel import KMatrixAccel, dispatch_capacity
from repro.core.kmatrix_accel import edge_freq as kmatrix_accel_edge_freq  # noqa: F401 (kernel-level re-export)
from repro.core.matrix_sketch import MatrixSketch
from repro.core.types import EdgeBatch
from repro.kernels.matrix_ingest import matrix_ingest
from repro.kernels.matrix_lookup import matrix_lookup
from repro.kernels.reach_closure import reach_step
from repro.kernels.embedding_bag import embedding_bag  # re-export


def interpret_mode() -> bool:
    """True unless JAX's default backend is a TPU.  Asked at call time, never
    at import, so the answer tracks the backend JAX actually started."""
    return jax.default_backend() != "tpu"


def _pad_edges(x: jax.Array, block: int, fill=0) -> jax.Array:
    b = x.shape[-1]
    pad = (-b) % block
    if pad == 0:
        return x
    widths = [(0, 0)] * (x.ndim - 1) + [(0, pad)]
    return jnp.pad(x, widths, constant_values=fill)


# --------------------------------------------------------------------------
# Global (d, w, w) matrix sketches: P = 1
# --------------------------------------------------------------------------

def accel_matrix_ingest(sk: MatrixSketch, batch: EdgeBatch,
                        *, block_b: int = 256) -> MatrixSketch:
    hi = fastrange(sk.hashes.mix(batch.src), sk.w)  # [d, B]
    hj = fastrange(sk.hashes.mix(batch.dst), sk.w)
    hi = _pad_edges(hi, block_b)[:, None, :]  # [d, 1, C]
    hj = _pad_edges(hj, block_b)[:, None, :]
    wt = _pad_edges(batch.weight, block_b)[None, :]  # [1, C]
    table = matrix_ingest(sk.table[:, None], hi, hj, wt, block_b=block_b,
                          interpret=interpret_mode())[:, 0]
    return sk.replace(table=table)


def accel_matrix_edge_freq(sk: MatrixSketch, src: jax.Array, dst: jax.Array,
                           *, block_q: int = 256) -> jax.Array:
    hi = _pad_edges(fastrange(sk.hashes.mix(src), sk.w), block_q)[:, None, :]
    hj = _pad_edges(fastrange(sk.hashes.mix(dst), sk.w), block_q)[:, None, :]
    est = matrix_lookup(sk.table[:, None], hi, hj, block_q=block_q,
                        interpret=interpret_mode())
    return est[0, : src.shape[-1]]


def accel_reach_closure(table: jax.Array, *, block: int = 128,
                        n_steps: int | None = None) -> jax.Array:
    """Boolean closure of every layer of int32[d, w, w] -> bool[d, w, w]."""
    d, w, _ = table.shape
    pad = (-w) % block
    adj = (table > 0).astype(jnp.float32)
    adj = jnp.pad(adj, ((0, 0), (0, pad), (0, pad)))
    wp = w + pad
    eye = jnp.eye(wp, dtype=jnp.float32)
    reach = jnp.minimum(adj + eye[None], 1.0)
    steps = n_steps if n_steps is not None else max(1, (w - 1).bit_length())
    step = functools.partial(reach_step, block=block,
                             interpret=interpret_mode())
    for _ in range(steps):
        reach = jax.vmap(step)(reach)
    return reach[:, :w, :w] > 0.5


# --------------------------------------------------------------------------
# kMatrix width-class layout
# --------------------------------------------------------------------------
#
# The ``KMatrixAccel`` state and its pure-jnp query/merge/relayout surface
# live in ``repro.core.kmatrix_accel`` (the sketch-protocol module the
# serving/runtime layers consume).  This file owns only the Pallas-backed
# ingest dispatch; the names below are re-exported for kernel-level callers.


def _dispatch(sk: KMatrixAccel, batch: EdgeBatch, capacity: int):
    """Bucket edges into per-partition rectangles (P, C) + overflow mask.

    Returns (slot, part, in_capacity): slot[e] is the edge's rank within its
    partition (stable), computed with one argsort — the TPU-friendly
    alternative to atomic counters.
    """
    p = sk.route.lookup(batch.src)  # [B]
    p = jnp.where(batch.weight > 0, p, jnp.int32(sk.route.n_partitions))  # park padding
    order = jnp.argsort(p)  # stable
    p_sorted = p[order]
    # rank within each partition = position - first position of that partition
    b = p.shape[0]
    pos = jnp.arange(b, dtype=jnp.int32)
    is_start = jnp.concatenate([jnp.ones(1, bool), p_sorted[1:] != p_sorted[:-1]])
    start_pos = jnp.where(is_start, pos, 0)
    start_of_group = jax.lax.associative_scan(jnp.maximum, start_pos)
    rank_sorted = pos - start_of_group
    rank = jnp.zeros_like(rank_sorted).at[order].set(rank_sorted)
    in_cap = (rank < capacity) & (batch.weight > 0)
    return p, rank, in_cap


def kmatrix_accel_ingest(sk: KMatrixAccel, batch: EdgeBatch,
                         *, capacity: int | None = None,
                         block_b: int = 128) -> KMatrixAccel:
    """Exact batched ingest: per-class Pallas matmul ingest for edges within
    capacity, in-jit scatter fallback for the overflow tail (no drops)."""
    b = batch.size
    if capacity is None:
        # sized from the partition plan's banded load (hottest partition's
        # expected share of the batch), NOT a uniform 2B/P — on skewed
        # streams the hot partition's load exceeds 2B/P by the skew factor
        # and every excess edge would pay the scatter fallback
        capacity = dispatch_capacity(sk, b, block_b)
    capacity = -(-capacity // block_b) * block_b

    p, rank, in_cap = _dispatch(sk, batch, capacity)
    d = sk.depth
    mix_src = sk.hashes.mix(batch.src)  # [d, B] uint32
    mix_dst = sk.hashes.mix(batch.dst)

    pools = list(sk.pools)
    for c, (w_c, p_c) in enumerate(zip(sk.class_widths, sk.class_counts)):
        if p_c == 0:
            continue
        sel = in_cap & (sk.part_class[p] == c)
        q = jnp.where(sel, sk.part_index[p], 0)
        # Park unselected edges at slot == capacity: out of bounds, dropped.
        # (Parking *in bounds* would let a parked .set(0) race a real edge.)
        slot = jnp.where(sel, rank, capacity)
        hi = fastrange(mix_src, w_c)  # [d, B]
        hj = fastrange(mix_dst, w_c)
        # Scatter edges into the (P_c, C) rectangle (weight 0 elsewhere).
        hi_r = jnp.zeros((d, p_c, capacity), jnp.int32).at[:, q, slot].set(
            jnp.where(sel[None], hi, 0), mode="drop")
        hj_r = jnp.zeros((d, p_c, capacity), jnp.int32).at[:, q, slot].set(
            jnp.where(sel[None], hj, 0), mode="drop")
        wt_r = jnp.zeros((p_c, capacity), jnp.int32).at[q, slot].add(
            jnp.where(sel, batch.weight, 0), mode="drop")
        pools[c] = matrix_ingest(pools[c], hi_r, hj_r, wt_r,
                                 block_b=block_b, interpret=interpret_mode())

    # Overflow tail: exact scatter (rare; only when a partition exceeds cap).
    # The tally is surfaced as sk.overflow so capacity regressions show up
    # in runtime metrics instead of silently eating scatter-fallback cost.
    over = (~in_cap) & (batch.weight > 0)
    overflow = sk.overflow + jnp.sum(over.astype(sk.overflow.dtype))
    w_p = sk.part_width[p]
    hi_o = fastrange(mix_src, w_p)
    hj_o = fastrange(mix_dst, w_p)
    wts_o = jnp.where(over, batch.weight, 0)
    cls_o = sk.part_class[p]
    idx_o = sk.part_index[p]
    for c, (w_c, p_c) in enumerate(zip(sk.class_widths, sk.class_counts)):
        if p_c == 0:
            continue
        sel = over & (cls_o == c)
        rows = jnp.arange(d, dtype=jnp.int32)[:, None]
        pools[c] = pools[c].at[
            rows, jnp.where(sel, idx_o, 0)[None], hi_o, hj_o
        ].add(jnp.where(sel, wts_o, 0)[None], mode="drop")

    if sk.conn_w > 0:
        ci = fastrange(mix_src, sk.conn_w)
        cj = fastrange(mix_dst, sk.conn_w)
        rows = jnp.arange(d, dtype=jnp.int32)[:, None]
        conn = sk.conn.at[rows, ci, cj].add(batch.weight[None])
    else:
        conn = sk.conn
    return sk.replace(pools=tuple(pools), conn=conn, overflow=overflow)
