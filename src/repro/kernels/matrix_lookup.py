"""Pallas TPU kernel: batched sketch point-queries (gather + min over layers).

Same one-hot MXU trick as matrix_ingest, inverted: the addressed cell value
for query q is

    val[q] = sum_i U[q,i] * ( sum_j M[i,j] * V[q,j] ) = M[hi[q], hj[q]]

computed as ``(U^T ⊙ (M @ V^T))`` summed over rows, with both one-hots built
transposed (query axis on lanes) so the slot vectors stay lane rows and the
contraction is a plain M @ B.

Grid is (P, C/TQ, d) with the *layer* axis innermost so the min-accumulator
tile stays resident while layers stream through the MXU.  Slots enter as
[d, P, 1, C] rows and the result leaves as [P, 1, C] (a Mosaic block must
span its last two dims whole or tile them by (8, 128); the singleton axis
makes one partition row a legal block for any P).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_INT32_MAX = jnp.iinfo(jnp.int32).max


def _lookup_kernel(hi_ref, hj_ref, pool_ref, out_ref):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        out_ref[...] = jnp.full_like(out_ref, _INT32_MAX)

    w = pool_ref.shape[-1]
    tq = hi_ref.shape[-1]
    hi = hi_ref[0, 0]  # (1, TQ)
    hj = hj_ref[0, 0]
    slots = jax.lax.broadcasted_iota(jnp.int32, (w, tq), 0)
    u_t = (slots == hi).astype(jnp.float32)  # (w, TQ)
    v_t = (slots == hj).astype(jnp.float32)
    m = pool_ref[0, 0].astype(jnp.float32)  # (w, w)
    mv = jax.lax.dot(m, v_t, precision=jax.lax.Precision.HIGHEST,
                     preferred_element_type=jnp.float32)  # (w, TQ)
    vals = jnp.sum(u_t * mv, axis=0, keepdims=True).astype(out_ref.dtype)
    out_ref[0] = jnp.minimum(out_ref[0], vals)


@functools.partial(jax.jit, static_argnames=("block_q", "interpret"))
def matrix_lookup(
    pool: jax.Array,
    hi: jax.Array,
    hj: jax.Array,
    *,
    block_q: int = 256,
    interpret: bool,
) -> jax.Array:
    """min_r pool[r, p, hi[r,p,c], hj[r,p,c]] -> int32[P, C]. See ref.py."""
    d, p, w, _ = pool.shape
    c = hi.shape[-1]
    assert c % block_q == 0, (c, block_q)
    grid = (p, c // block_q, d)
    slot = pl.BlockSpec((1, 1, 1, block_q), lambda q, b, r: (r, q, 0, b))
    out = pl.pallas_call(
        _lookup_kernel,
        grid=grid,
        in_specs=[
            slot,
            slot,
            pl.BlockSpec((1, 1, w, w), lambda q, b, r: (r, q, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q), lambda q, b, r: (q, 0, b)),
        out_shape=jax.ShapeDtypeStruct((p, 1, c), pool.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="matrix_lookup",
    )(hi[:, :, None, :], hj[:, :, None, :], pool)
    return out[:, 0, :]
