"""Distributed kMatrix: the paper's technique scaled out (paper §VI lists
"data partitioning across machines" as future work — this implements it).

Two orthogonal distribution modes, composable on a ("data", "model") mesh:

  DATA-PARALLEL (exact, embarrassingly so): counters are additive, so each
  data shard sketches its sub-stream into a local replica and queries psum
  across the axis (or merge periodically).  This is `dp_ingest` +
  `dp_edge_freq` under shard_map.

  PARTITION-PARALLEL (the kMatrix structure IS a routing table): partitions
  are sharded over the "model" axis like MoE experts; each device owns
  ``P / n_model`` partition slabs.  Edges route by source vertex ->
  partition -> owner device.  Two dispatch strategies:

    * "allgather" — every device all-gathers the edge batch and ingests
      only edges owned locally.  EXACT; wire bytes = B * n_model. This is
      the baseline collective schedule.
    * "a2a" — bucket edges per owner with a static capacity and exchange
      via all_to_all; wire bytes = B * capacity_factor.  Overflow beyond
      capacity is counted and returned (a production deployment loops the
      tail; the benchmark asserts zero drops at cf=2).

  EXPERIMENTS.md §Perf compares the two collective schedules' roofline
  terms — a2a moves ~n_model x fewer bytes and wins whenever the stream is
  well spread across partitions (which the banded partitioner guarantees by
  construction: bands are equal-count).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.common.hashing import fastrange
from repro.core.kmatrix import KMatrix
from repro.core.types import EdgeBatch


# ----------------------------------------------------------- data parallel

def make_dp_ingest(sk_template: KMatrix, mesh, axis: str = "data"):
    """Returns ingest(replicated_pool_stack, batch_shard) under shard_map.

    Pool state is stored SHARDED over the data axis as independent replicas
    (shape [d, pool]); merge happens at query time via psum.
    """

    def local_ingest(pool, conn, src, dst, wt):
        sk = sk_template.replace(pool=pool, conn=conn)
        from repro.core import kmatrix

        new = kmatrix.ingest(sk, EdgeBatch(src=src, dst=dst, weight=wt))
        return new.pool, new.conn

    d, pool_size = sk_template.pool.shape
    return jax.jit(shard_map(
        local_ingest,
        mesh=mesh,
        in_specs=(P(axis, None), P(axis, None, None), P(axis), P(axis), P(axis)),
        out_specs=(P(axis, None), P(axis, None, None)),
    ))


def make_dp_edge_freq(sk_template: KMatrix, mesh, axis: str = "data"):
    """Query across data-parallel replicas: psum partial counters, then min."""

    def local_query(pool, conn, src, dst):
        from repro.core import kmatrix

        pool = jax.lax.psum(pool, axis)
        sk = sk_template.replace(pool=pool, conn=conn)
        est = kmatrix.edge_freq(sk, src, dst)
        return est

    return jax.jit(shard_map(
        local_query,
        mesh=mesh,
        in_specs=(P(axis, None), P(axis, None, None), P(None), P(None)),
        out_specs=P(None),
    ))


# ------------------------------------------------------ partition parallel

def build_owner_map(sk: KMatrix, n_model: int) -> np.ndarray:
    """Assign partitions to model-axis devices, balancing total slab area."""
    widths = np.asarray(sk.route.widths)
    areas = widths.astype(np.int64) ** 2
    order = np.argsort(-areas)  # biggest first, greedy bin pack
    owner = np.zeros(len(widths), np.int32)
    load = np.zeros(n_model, np.int64)
    for p in order:
        dev = int(np.argmin(load))
        owner[p] = dev
        load[dev] += areas[p]
    return owner


def make_pp_ingest(
    sk_template: KMatrix,
    mesh,
    *,
    mode: str = "a2a",
    capacity_factor: float = 2.0,
    data_axis=None,  # str or tuple; default: every non-model axis
    model_axis: str = "model",
):
    """Partition-parallel ingest under shard_map.

    State layout: each model shard holds the FULL flat pool buffer but only
    writes its owned slabs (memory-lean layouts would slice the pool per
    owner; we keep the flat buffer so estimates stay one gather — the
    unwritten regions are zeros and a psum(model) at query time
    reconstitutes the global pool).

    Returns (ingest_fn, owner_map). ingest_fn(pool, conn, src, dst, wt)
    with pool sharded P(model_axis-replicated...) — see specs below — and
    edges sharded over the data axis; returns updated (pool, conn, dropped).
    """
    if data_axis is None:
        data_axis = tuple(a for a in mesh.axis_names if a != model_axis)
    data_axes = (data_axis,) if isinstance(data_axis, str) else tuple(data_axis)
    n_model = mesh.shape[model_axis]
    owner_np = build_owner_map(sk_template, n_model)
    owner_map = jnp.asarray(owner_np)
    d = sk_template.depth

    # State layout: every (data, model) device holds its own (d, pool) and
    # (d, cw, cw) replica rows — stacked over BOTH axes — so the out-specs
    # never claim replication the program doesn't enforce. Queries psum the
    # slab-disjoint pools over both axes. conn writes are gated to model
    # rank 0 (each edge must count once, and every model rank in a data row
    # sees the same edge shard).

    def local(pool, conn, src, dst, wt):
        my_dev = jax.lax.axis_index(model_axis)
        from repro.core import kmatrix

        def conn_update(conn):
            if sk_template.conn_w == 0:
                return conn
            ci = fastrange(sk_template.hashes.mix(src), sk_template.conn_w)
            cj = fastrange(sk_template.hashes.mix(dst), sk_template.conn_w)
            rows = jnp.arange(d, dtype=jnp.int32)[:, None]
            gate = (my_dev == 0).astype(conn.dtype)
            return conn.at[rows, ci, cj].add(wt[None] * gate)

        # Edges arrive replicated along the model axis (in_spec P(data)):
        # each model rank claims its own 1/n_model slice, so every edge is
        # processed by exactly one rank per data row.
        b = src.shape[0]
        b_m = b // n_model
        sl = lambda x: jax.lax.dynamic_slice_in_dim(x, my_dev * b_m, b_m)
        src_m, dst_m, wt_m = sl(src), sl(dst), sl(wt)

        if mode == "allgather":
            # classic dispatch: gather every rank's slice, keep owned edges
            src_all = jax.lax.all_gather(src_m, model_axis, tiled=True)
            dst_all = jax.lax.all_gather(dst_m, model_axis, tiled=True)
            wt_all = jax.lax.all_gather(wt_m, model_axis, tiled=True)
            p = sk_template.route.lookup(src_all)
            mine = owner_map[p] == my_dev
            wt_mine = jnp.where(mine, wt_all, 0)
            sk = sk_template.replace(pool=pool, conn=jnp.zeros_like(conn))
            new = kmatrix.ingest(
                sk, EdgeBatch(src=src_all, dst=dst_all, weight=wt_mine)
            )
            dropped = jnp.zeros((), jnp.int32)
            return new.pool, conn_update(conn), dropped

        # ---- a2a: bucket my slice by owner, exchange, ingest local -------
        cap = int(b_m * capacity_factor / n_model)
        cap = max(cap, 8)
        p = sk_template.route.lookup(src_m)
        own = jnp.where(wt_m > 0, owner_map[p], n_model)  # park padding
        order = jnp.argsort(own)
        own_s = own[order]
        pos = jnp.arange(b_m, dtype=jnp.int32)
        is_start = jnp.concatenate([jnp.ones(1, bool), own_s[1:] != own_s[:-1]])
        start = jax.lax.cummax(jnp.where(is_start, pos, 0))
        rank_s = pos - start
        rank = jnp.zeros_like(rank_s).at[order].set(rank_s)
        keep = (rank < cap) & (own < n_model)
        slot = jnp.where(keep, rank, cap)
        buck = lambda x, fill: jnp.full((n_model, cap), fill, x.dtype).at[
            jnp.minimum(own, n_model - 1), slot
        ].set(jnp.where(keep, x, fill), mode="drop")
        src_b = buck(src_m, 0)
        dst_b = buck(dst_m, 0)
        wt_b = jnp.full((n_model, cap), 0, wt_m.dtype).at[
            jnp.minimum(own, n_model - 1), slot
        ].set(jnp.where(keep, wt_m, 0), mode="drop")
        # exchange: device m receives bucket m from every model peer
        src_r = jax.lax.all_to_all(src_b, model_axis, 0, 0, tiled=True)
        dst_r = jax.lax.all_to_all(dst_b, model_axis, 0, 0, tiled=True)
        wt_r = jax.lax.all_to_all(wt_b, model_axis, 0, 0, tiled=True)
        sk = sk_template.replace(pool=pool, conn=jnp.zeros_like(conn))
        new = kmatrix.ingest(
            sk,
            EdgeBatch(src=src_r.reshape(-1), dst=dst_r.reshape(-1),
                      weight=wt_r.reshape(-1)),
        )
        dropped = jnp.sum((~keep & (own < n_model)).astype(jnp.int32))
        dropped = jax.lax.psum(dropped, model_axis)
        for ax in data_axes:
            dropped = jax.lax.psum(dropped, ax)
        dropped = dropped // n_model  # model ranks of a row count same drops
        return new.pool, conn_update(conn), dropped

    both = data_axes + (model_axis,)
    fn = jax.jit(shard_map(
        local,
        mesh=mesh,
        in_specs=(
            P(both, None),  # pool: per-device replica rows (stacked)
            P(both, None, None),  # conn: per-device rows, model-0-gated
            P(data_axes),
            P(data_axes),
            P(data_axes),
        ),
        out_specs=(P(both, None), P(both, None, None), P()),
    ))
    return fn, owner_np


def make_pp_edge_freq(sk_template: KMatrix, mesh, *,
                      data_axis=None, model_axis: str = "model"):
    """Query on partition-parallel state: psum the slab-disjoint pools over
    both axes (model shards are slab-disjoint, data shards are additive)."""
    if data_axis is None:
        data_axis = tuple(a for a in mesh.axis_names if a != model_axis)
    data_axes = (data_axis,) if isinstance(data_axis, str) else tuple(data_axis)

    def local(pool, conn, src, dst):
        from repro.core import kmatrix

        pool = jax.lax.psum(pool, model_axis)
        conn = jax.lax.psum(conn, model_axis)
        for ax in data_axes:
            pool = jax.lax.psum(pool, ax)
            conn = jax.lax.psum(conn, ax)
        sk = sk_template.replace(pool=pool, conn=conn)
        return kmatrix.edge_freq(sk, src, dst)

    both = data_axes + (model_axis,)
    return jax.jit(shard_map(
        local,
        mesh=mesh,
        in_specs=(P(both, None), P(both, None, None), P(None), P(None)),
        out_specs=P(None),
    ))
