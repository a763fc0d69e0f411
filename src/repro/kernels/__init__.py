"""Pallas TPU kernels for the perf-critical compute layers.

Each kernel ships three pieces (see EXAMPLE.md):
  <name>.py  — pl.pallas_call + explicit BlockSpec VMEM tiling
  ops.py     — jit'd wrappers binding kernels to sketch/model state
  ref.py     — pure-jnp oracles defining exact semantics

Kernels:
  matrix_ingest  — sketch ingest as one-hot MXU matmul accumulation
  reach_closure  — tiled boolean matmul squaring (reachability)
  embedding_bag  — scalar-prefetch row-gather + segment reduce (recsys)
"""
from repro.kernels.matrix_ingest import matrix_ingest
from repro.kernels.reach_closure import reach_step
from repro.kernels.embedding_bag import embedding_bag
from repro.kernels import ops, ref

__all__ = [
    "matrix_ingest",
    "reach_step",
    "embedding_bag",
    "ops",
    "ref",
]
