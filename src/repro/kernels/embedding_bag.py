"""Pallas TPU kernel: fixed-arity EmbeddingBag (gather rows + reduce).

JAX has no native EmbeddingBag; the recsys substrate builds it from
``jnp.take`` + reduce.  On TPU the hot path is the HBM row gather — this
kernel uses a *scalar-prefetch* grid so each (bag, field) step's BlockSpec
index_map addresses table row ``idx[b, f]`` directly: Pallas double-buffers
the row DMAs (HBM -> VMEM) against the running bag accumulation, which is
exactly how production TPU embedding layers (and the row-gather half of
FBGEMM's TBE) are structured.

Grid (B, F), field axis innermost: out tile (1, D) stays resident per bag;
each step streams one table row through it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _bag_kernel(idx_ref, wt_ref, row_ref, out_ref, *, weighted: bool):
    f = pl.program_id(1)

    @pl.when(f == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    row = row_ref[...]
    if weighted:
        row = row * wt_ref[0, f]
    out_ref[...] += row


@functools.partial(jax.jit, static_argnames=("interpret",))
def embedding_bag(
    table: jax.Array,
    idx: jax.Array,
    weights: jax.Array | None = None,
    *,
    interpret: bool,
) -> jax.Array:
    """out[b] = sum_f weights[b,f] * table[idx[b,f]].

    table: f32[V, D] (D lane-aligned for TPU), idx: int32[B, F],
    weights: f32[B, F] or None. Returns f32[B, D]. See ref.py oracle.
    """
    b, f = idx.shape
    v, d = table.shape
    weighted = weights is not None
    if weights is None:
        weights = jnp.ones((b, f), dtype=table.dtype)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, f),
        in_specs=[
            pl.BlockSpec((1, f), lambda i, j, idx_ref: (i, 0)),
            pl.BlockSpec((1, d), lambda i, j, idx_ref: (idx_ref[i * f + j], 0)),
        ],
        out_specs=pl.BlockSpec((1, d), lambda i, j, idx_ref: (i, 0)),
    )
    return pl.pallas_call(
        functools.partial(_bag_kernel, weighted=weighted),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, d), table.dtype),
        interpret=interpret,
    )(idx.reshape(-1), weights, table)
