"""Exactness of the distributed ingest modes against a one-device ingest.

Counters are additive, so data-parallel ingest and partition-parallel
all-gather ingest must reproduce a single-device sketch counter for counter
once their per-device rows are summed.  The a2a mode drops edges past its
bucket capacity (and counts them), so it may only under-count.
``check_modes`` runs all three over one stream on a ("data", "model") mesh
and reports each verdict.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import kmatrix
from repro.core.kmatrix import KMatrix
from repro.distributed.sketch_parallel import (make_dp_edge_freq,
                                               make_dp_ingest,
                                               make_pp_edge_freq,
                                               make_pp_ingest)


def check_modes(sk0: KMatrix, stream, mesh, qs, qd, *,
                a2a_capacity_factor: float = 2.0) -> dict:
    """Ingest ``stream`` from ``sk0`` in every mode on ``mesh`` and compare
    counters and ``edge_freq(qs, qd)`` with one device's ingest.

    Returns ``dp_exact`` and ``pp_allgather_exact`` (pools, conn matrices and
    estimates equal), ``a2a_dropped`` (edges past capacity),
    ``a2a_never_overcounts`` and ``owner_counts`` (partitions per model rank).
    """
    qs, qd = jnp.asarray(qs), jnp.asarray(qd)
    ref, ingest_one = sk0, jax.jit(kmatrix.ingest)
    for b in stream:
        ref = ingest_one(ref, b)
    ref_pool, ref_conn = np.asarray(ref.pool), np.asarray(ref.conn)
    ref_est = np.asarray(kmatrix.edge_freq(ref, qs, qd))
    d = sk0.depth

    def zeros(n_rep):  # per-device replica rows, stacked
        return (jnp.zeros((n_rep * d,) + sk0.pool.shape[1:], jnp.int32),
                jnp.zeros((n_rep * d,) + sk0.conn.shape[1:], jnp.int32))

    def exact(pool, conn, est, n_rep):
        summed = lambda x: np.asarray(x).reshape(
            (n_rep, d) + x.shape[1:]).sum(axis=0)
        return bool(np.array_equal(summed(pool), ref_pool)
                    and np.array_equal(summed(conn), ref_conn)
                    and np.array_equal(np.asarray(est), ref_est))

    n_data, n_rep = mesh.shape["data"], mesh.devices.size
    verdict = {}
    with jax.set_mesh(mesh):
        ingest, query = make_dp_ingest(sk0, mesh), make_dp_edge_freq(sk0, mesh)
        pool, conn = zeros(n_data)
        for b in stream:
            pool, conn = ingest(pool, conn, b.src, b.dst, b.weight)
        verdict["dp_exact"] = exact(pool, conn, query(pool, conn, qs, qd),
                                    n_data)

        query = make_pp_edge_freq(sk0, mesh)
        ingest, _ = make_pp_ingest(sk0, mesh, mode="allgather")
        pool, conn = zeros(n_rep)
        for b in stream:
            pool, conn, _ = ingest(pool, conn, b.src, b.dst, b.weight)
        verdict["pp_allgather_exact"] = exact(
            pool, conn, query(pool, conn, qs, qd), n_rep)

        ingest, owner = make_pp_ingest(sk0, mesh, mode="a2a",
                                       capacity_factor=a2a_capacity_factor)
        pool, conn = zeros(n_rep)
        dropped = 0
        for b in stream:
            pool, conn, drop = ingest(pool, conn, b.src, b.dst, b.weight)
            dropped += int(drop)
        est = np.asarray(query(pool, conn, qs, qd))
    verdict["a2a_dropped"] = dropped
    verdict["a2a_never_overcounts"] = bool((est <= ref_est).all())
    verdict["owner_counts"] = np.bincount(
        owner, minlength=mesh.shape["model"]).tolist()
    return verdict
