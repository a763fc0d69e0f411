"""Pure-jnp oracles for every Pallas kernel in this package.

Each function defines the *exact* semantics its kernel must reproduce
(tests sweep shapes/dtypes and assert_allclose kernel-vs-oracle, with the
kernel run in interpret mode on CPU).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def matrix_ingest_ref(pool: jax.Array, hi: jax.Array, hj: jax.Array,
                      wt: jax.Array) -> jax.Array:
    """Scatter-add edge counts into per-partition count matrices.

    pool: int32[d, P, w, w]   hi/hj: int32[d, P, C]   wt: int32[P, C]
    For every layer r, partition p, slot c:
        pool[r, p, hi[r,p,c], hj[r,p,c]] += wt[p, c]
    (wt == 0 marks padding / unused capacity slots.)
    """
    d, p, w, _ = pool.shape
    rows = jnp.arange(d, dtype=jnp.int32)[:, None, None]
    parts = jnp.arange(p, dtype=jnp.int32)[None, :, None]
    return pool.at[rows, parts, hi, hj].add(
        jnp.broadcast_to(wt[None], hi.shape).astype(pool.dtype)
    )


def reach_step_ref(reach: jax.Array) -> jax.Array:
    """One boolean-closure squaring step: R <- min(R @ R, 1), R: f32[w, w]."""
    return jnp.minimum(
        jax.lax.dot(reach, reach, preferred_element_type=jnp.float32), 1.0
    )


def reach_closure_ref(adj: jax.Array, n_steps: int) -> jax.Array:
    """Reflexive-transitive closure via ``n_steps`` squarings. adj: f32[w,w]."""
    w = adj.shape[-1]
    reach = jnp.minimum(adj + jnp.eye(w, dtype=adj.dtype), 1.0)
    for _ in range(n_steps):
        reach = reach_step_ref(reach)
    return reach


def embedding_bag_ref(table: jax.Array, idx: jax.Array,
                      weights: jax.Array | None = None) -> jax.Array:
    """Fixed-arity embedding bag: out[b] = sum_f w[b,f] * table[idx[b,f]].

    table: f32[V, D]   idx: int32[B, F]   weights: f32[B, F] or None
    """
    rows = table[idx]  # [B, F, D]
    if weights is not None:
        rows = rows * weights[..., None]
    return rows.sum(axis=1)
