"""Pallas TPU kernel: one boolean transitive-closure squaring step.

Reachability on matrix sketches (queries.py) is log2(w) squarings of a
boolean adjacency: R <- min(R @ R, 1).  This kernel is a classic tiled
matmul with a clamp epilogue; ops.py drives the outer squaring loop (each
step is one pallas_call — the data dependency between steps is global, so
steps cannot fuse).

Grid (M/TM, N/TN, K/TK), K innermost; the accumulator tile is f32 in VMEM
and the clamp runs on the final K step only.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _closure_step_kernel(a_ref, b_ref, out_ref, acc_ref, *, n_k: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot(
        a_ref[...], b_ref[...], preferred_element_type=jnp.float32
    )

    @pl.when(k == n_k - 1)
    def _epilogue():
        out_ref[...] = jnp.minimum(acc_ref[...], 1.0).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def reach_step(reach: jax.Array, *, block: int = 256,
               interpret: bool) -> jax.Array:
    """One squaring step R <- min(R @ R, 1). reach: f32[w, w], w % block == 0."""
    w = reach.shape[-1]
    assert w % block == 0, (w, block)
    n_k = w // block
    grid = (w // block, w // block, n_k)
    return pl.pallas_call(
        functools.partial(_closure_step_kernel, n_k=n_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block, block), lambda i, j, k: (i, k)),
            pl.BlockSpec((block, block), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((block, block), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((w, w), reach.dtype),
        scratch_shapes=[pltpu.VMEM((block, block), jnp.float32)],
        interpret=interpret,
    )(reach, reach)
