"""Pallas TPU kernel: batched sketch ingest via one-hot MXU accumulation.

Hardware adaptation (DESIGN.md §TPU-adaptation): the GPU-native formulation
of sketch ingest is an atomic scatter-add — one random HBM write per (edge,
layer).  TPUs have no atomics and serialize XLA scatters, so we *reformulate
counting as matrix multiplication*: for an edge tile with row-slots ``hi``,
column-slots ``hj`` and weights ``wt``,

    increment = U^T @ (V * wt[:, None]),   U = onehot(hi), V = onehot(hj)

adds exactly ``wt[e]`` at cell ``(hi[e], hj[e])`` for every edge ``e`` in the
tile — a (w x TB) @ (TB x w) contraction that runs on the 128x128 systolic
MXU at full clip instead of a serialized scatter pipeline.  The contraction
runs at fp32 precision, so it is exact while a cell's increment per tile
stays below 2^24; the result is cast and added into the resident int32 tile.

Layout: ``pool`` is [d, P, w, w] — d hash layers, P partitions (P=1 recovers
plain TCM/gMatrix; P>1 is the kMatrix width-class layout).  Grid is
(d, P, w/TR, C/TB) with the edge-tile axis innermost: each (layer,
partition, row-block) out block stays resident in VMEM while every edge tile
streams through it.  Both one-hots are built transposed — (rows, TB) with
the edge axis on lanes — so the slot vectors are plain lane rows and the
contraction is the A @ B^T form the MXU takes natively.

Edge slots enter as [d, P, 1, C] rows: Mosaic blocks must match the (8, 128)
tiling in their last two dims or span them whole, so the singleton sublane
axis lets one (layer, partition) row be a block for any P.

VMEM: past w = 512 the out tile is row-blocked to TR ~ 2^18 / w rows (a
multiple of 8, <= 1 MiB of int32), so the resident pool/out blocks stay
~4 MiB double-buffered at w = 1024 and beyond; the (w, TB) f32 column
one-hot adds w * TB * 4 B.  When TR does not divide w (widths that are not
powers of two) the row axis is zero-padded to a whole number of blocks and
cut back afterwards; no edge addresses a padded row.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Out-tile cells per row block: TR * w int32 stays at about 1 MiB.
_TILE_CELLS = 1 << 18

# Widest class whose ingest step has been compiled for a TPU v5e (with
# block_b=128).  ``KMatrixAccel`` refuses wider classes at creation rather
# than fall back to a scatter path nobody asked for.
MAX_WIDTH = 16384


def _row_block(w: int) -> int:
    """Rows of the (TR, w) out block for a class of width ``w``: the whole
    tile up to 2^18 cells, else the fewest row blocks of at most ~2^18 cells,
    each a multiple of 8 rows (Mosaic's sublane tiling)."""
    if w * w <= _TILE_CELLS:
        return w
    n_blocks = -(-w // max(8, _TILE_CELLS // w))
    return (-(-w // n_blocks) + 7) // 8 * 8


def _ingest_kernel(hi_ref, hj_ref, wt_ref, pool_ref, out_ref):
    @pl.when(pl.program_id(3) == 0)
    def _init():
        out_ref[...] = pool_ref[...]

    tr, w = out_ref.shape[-2:]
    tb = hi_ref.shape[-1]
    row0 = pl.program_id(2) * tr
    hi = hi_ref[0, 0]  # (1, TB)
    hj = hj_ref[0, 0]
    wt = wt_ref[0].astype(jnp.float32)  # (1, TB)
    rows = jax.lax.broadcasted_iota(jnp.int32, (tr, tb), 0) + row0
    cols = jax.lax.broadcasted_iota(jnp.int32, (w, tb), 0)
    u_t = (rows == hi).astype(jnp.float32)  # (TR, TB)
    v_t = jnp.where(cols == hj, wt, 0.0)  # (w, TB)
    inc = jax.lax.dot_general(
        u_t, v_t, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )  # (TR, w) = U^T @ (V * wt)
    out_ref[0, 0] += inc.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def matrix_ingest(
    pool: jax.Array,
    hi: jax.Array,
    hj: jax.Array,
    wt: jax.Array,
    *,
    block_b: int = 256,
    interpret: bool,
) -> jax.Array:
    """pool[r,p,hi[r,p,c],hj[r,p,c]] += wt[p,c] for all (r,p,c). See ref.py.

    Shapes: pool int32[d,P,w,w], hi/hj int32[d,P,C], wt int32[P,C].
    C must be a multiple of ``block_b`` (ops.py pads with wt=0 slots).
    """
    d, p, w, _ = pool.shape
    c = hi.shape[-1]
    assert c % block_b == 0, (c, block_b)
    tr = _row_block(w)
    w_rows = -(-w // tr) * tr
    if w_rows != w:
        pool = jnp.pad(pool, ((0, 0), (0, 0), (0, w_rows - w), (0, 0)))
    grid = (d, p, w_rows // tr, c // block_b)
    slot = pl.BlockSpec((1, 1, 1, block_b), lambda r, q, i, b: (r, q, 0, b))
    tile = pl.BlockSpec((1, 1, tr, w), lambda r, q, i, b: (r, q, i, 0))
    out = pl.pallas_call(
        _ingest_kernel,
        grid=grid,
        in_specs=[
            slot,
            slot,
            pl.BlockSpec((1, 1, block_b), lambda r, q, i, b: (q, 0, b)),
            tile,
        ],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="matrix_ingest",
    )(hi[:, :, None, :], hj[:, :, None, :], wt[:, None, :], pool)
    return out if w_rows == w else out[:, :, :w]
