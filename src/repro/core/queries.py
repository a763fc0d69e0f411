"""Type II query surface on matrix sketches (TCM / gMatrix / kMatrix).

Implements the query families from the TCM/gMatrix papers that the kMatrix
paper claims compatibility with:

  * edge frequency              (per-sketch ``edge_freq``)
  * node out/in aggregate       (row/col sums)
  * reachability                boolean transitive closure per layer; a pair
                                is declared reachable only if EVERY layer
                                agrees (one-sided error, like CountMin).
  * heavy nodes / heavy edges   vectorized "reverse" universe sweeps — the
                                gMatrix pairwise-independent hashing makes a
                                candidate scan sound; we batch it so scoring
                                a 1M-vertex universe is a few fused gathers.
  * path / subgraph weight      composition of edge queries.

The closure uses O(log w) boolean matrix squarings; squarings are float32
matmuls (MXU-friendly on TPU) thresholded back to {0,1}.
"""
from __future__ import annotations

import functools
import os
from typing import Callable

import jax
import jax.numpy as jnp

from repro.core import kmatrix as km
from repro.core import kmatrix_accel as kma
from repro.core import matrix_sketch as ms
from repro.obs.trace import get_trace_log


def _bool_closure(adj: jax.Array, max_hops: int | None = None) -> jax.Array:
    """Reflexive-transitive closure of a boolean adjacency matrix [w, w]."""
    w = adj.shape[-1]
    reach = (adj | jnp.eye(w, dtype=bool)).astype(jnp.float32)

    def body(_, r):
        return jnp.minimum(r @ r, 1.0)

    # _closure_steps is shared with the Pallas backend: both paths MUST
    # square the same number of times or their closures diverge
    reach = jax.lax.fori_loop(0, _closure_steps(w, max_hops), body, reach)
    return reach > 0.5


# --- engine-callable pure functions (explicit closure injection) -------------
#
# The O(log w) squaring cascade is the expensive half of a reachability query;
# the per-pair lookup is a few gathers.  Splitting them lets the serving
# engine compute ``build_closure`` ONCE per (tenant, epoch) and answer every
# subsequent reachability query against the cached closure (DESIGN.md
# §Serving).  The classic one-shot entry points below are thin wrappers.


def _closure_steps(w: int, max_hops: int | None) -> int:
    """Number of squarings covering paths of length ``max_hops`` (or any)."""
    return (max(1, (w - 1).bit_length()) if max_hops is None
            else max(1, max_hops.bit_length()))


@functools.partial(jax.jit, static_argnames=("max_hops",))
def _build_closure_jnp(adj_layers: jax.Array,
                       max_hops: int | None = None) -> jax.Array:
    return jax.vmap(lambda a: _bool_closure(a > 0, max_hops))(adj_layers)


@functools.partial(jax.jit, static_argnames=("n_steps", "block"))
def _build_closure_pallas(adj_layers: jax.Array, n_steps: int,
                          block: int) -> jax.Array:
    # imported lazily so the pure-jnp query surface never requires Pallas
    from repro.kernels.ops import accel_reach_closure

    return accel_reach_closure(adj_layers, block=block, n_steps=n_steps)


def closure_backend(backend: str | None = None) -> str:
    """Resolve the closure backend: explicit arg > $REPRO_CLOSURE_BACKEND >
    platform default (Pallas kernel on TPU, pure jnp elsewhere — off a TPU
    the Pallas path runs in the Pallas interpreter, which checks results
    only and is slower than XLA's fused matmuls, so it is opt-in there)."""
    backend = backend or os.environ.get("REPRO_CLOSURE_BACKEND") or (
        "pallas" if jax.default_backend() == "tpu" else "jnp")
    if backend not in ("jnp", "pallas"):
        raise ValueError(f"unknown closure backend {backend!r} "
                         "(expected 'jnp' or 'pallas')")
    return backend


def build_closure(adj_layers: jax.Array, max_hops: int | None = None, *,
                  backend: str | None = None) -> jax.Array:
    """Per-layer boolean closure: counter layers [d, w, w] -> bool [d, w, w].

    Backend dispatch (ROADMAP `kernels/reach_closure.py` item): ``"pallas"``
    drives the tiled MXU squaring kernel (``kernels.ops.accel_reach_closure``,
    interpret-mode off TPU), ``"jnp"`` the pure-XLA cascade.  Both compute
    the identical boolean fixpoint — squarings of a 0/1 float matrix are
    exact in f32 for w < 2^24 — and are parity-tested in tests/test_kernels.
    """
    with get_trace_log().span("kmatrix.engine.closure_build"):
        if closure_backend(backend) == "jnp":
            return _build_closure_jnp(adj_layers, max_hops)
        w = adj_layers.shape[-1]
        # pow-of-two tile <= 128 that covers small widths without overpadding
        block = min(128, 1 << max(3, (max(w, 2) - 1).bit_length()))
        return _build_closure_pallas(adj_layers, _closure_steps(w, max_hops),
                                     block)


def reachability_from_closure(closure: jax.Array, hi: jax.Array,
                              hj: jax.Array) -> jax.Array:
    """Pair lookup against a prebuilt closure.

    ``hi``/``hj`` are per-layer node slots [d, *S]; a pair is reachable only
    if EVERY layer agrees (one-sided error, like CountMin).
    """
    d = closure.shape[0]
    rows = jnp.arange(d, dtype=jnp.int32).reshape((d,) + (1,) * (hi.ndim - 1))
    return jnp.all(closure[rows, hi, hj], axis=0)


def closure_layers(sk) -> jax.Array:
    """The [d, w, w] adjacency layers a sketch uses for connectivity queries.

    Only matrix-shaped Type II sketches qualify; CountMin/gSketch hash the
    whole edge to one cell, so no adjacency structure exists to close over —
    rejecting them here beats returning silently meaningless reachability.
    """
    if isinstance(sk, (km.KMatrix, kma.KMatrixAccel)):
        assert sk.conn_w > 0, (
            "kMatrix built with conn_frac=0 cannot answer reachability")
        return sk.conn
    if isinstance(sk, ms.MatrixSketch):
        return sk.table
    raise ValueError(
        f"reachability is not answerable by {type(sk).__name__}: "
        "no [d, w, w] adjacency layers")


def reach_cells(sk, v: jax.Array) -> jax.Array:
    """Per-layer connectivity-matrix slot of vertex ``v`` -> int32[d, *S]."""
    if isinstance(sk, km.KMatrix):
        return km.conn_cells(sk, v)
    if isinstance(sk, kma.KMatrixAccel):
        return kma.conn_cells(sk, v)
    if isinstance(sk, ms.MatrixSketch):
        return ms.node_cells(sk, v)
    raise ValueError(
        f"reachability is not answerable by {type(sk).__name__}: "
        "no [d, w, w] adjacency layers")


def reachability(sk: ms.MatrixSketch, src: jax.Array, dst: jax.Array,
                 max_hops: int | None = None) -> jax.Array:
    """Estimated reachability src ->* dst. True may be a false positive
    (hash collisions merge nodes) but never a false negative."""
    closure = build_closure(sk.table, max_hops)  # [d,w,w]
    return reachability_from_closure(
        closure, ms.node_cells(sk, src), ms.node_cells(sk, dst))


def kmatrix_reachability(sk: km.KMatrix, src: jax.Array, dst: jax.Array,
                         max_hops: int | None = None) -> jax.Array:
    """Reachability on kMatrix via its global connectivity matrix."""
    closure = build_closure(closure_layers(sk), max_hops)
    return reachability_from_closure(
        closure, km.conn_cells(sk, src), km.conn_cells(sk, dst))


def heavy_nodes(
    node_freq_fn: Callable[[jax.Array], jax.Array],
    universe_size: int,
    threshold: float,
    *,
    chunk: int = 65536,
) -> tuple[jax.Array, jax.Array]:
    """Reverse sweep: score every vertex id in [0, universe) and return
    (ids, freqs) of those with estimated aggregate >= threshold.

    Returns dense arrays of length ``universe_size`` rounded up to ``chunk``
    with -1 ids on misses (static shapes; callers filter host-side).
    """
    n_chunks = -(-universe_size // chunk)
    padded = n_chunks * chunk

    def score(block_start):
        ids = block_start + jnp.arange(chunk, dtype=jnp.int32)
        freqs = node_freq_fn(ids)
        valid = (ids < universe_size) & (freqs >= threshold)
        return jnp.where(valid, ids, -1), jnp.where(valid, freqs, 0)

    starts = jnp.arange(n_chunks, dtype=jnp.int32) * chunk
    ids, freqs = jax.lax.map(score, starts)
    return ids.reshape(padded), freqs.reshape(padded)


def heavy_edges(
    edge_freq_fn: Callable[[jax.Array, jax.Array], jax.Array],
    cand_src: jax.Array,
    cand_dst: jax.Array,
    threshold: float,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Candidate-set heavy-edge query: mask + estimates for given pairs."""
    est = edge_freq_fn(cand_src, cand_dst)
    keep = est >= threshold
    return keep, est, jnp.where(keep, est, 0)


def path_weight(
    edge_freq_fn: Callable[[jax.Array, jax.Array], jax.Array],
    path_nodes: jax.Array,
) -> jax.Array:
    """Aggregate (sum of estimated frequencies) along a node path [k]."""
    return jnp.sum(edge_freq_fn(path_nodes[:-1], path_nodes[1:]))


def subgraph_weight(
    edge_freq_fn: Callable[[jax.Array, jax.Array], jax.Array],
    src: jax.Array,
    dst: jax.Array,
) -> jax.Array:
    """Total estimated weight of an explicit edge set."""
    return jnp.sum(edge_freq_fn(src, dst))
