"""The benchmark's edge stream: one lap of a statistically matched graph,
replayed lap after lap.

The generator is a copy of the program's ``streams/generators.py``
``SyntheticStream`` (Zipf source and destination skew over a seeded node
permutation, batch ``i`` a pure function of ``(graph_seed, i)``), kept here so
that a change to the program's generator cannot move the yardstick.  The
graph (which edges, which vertices are hot) is fixed by the configuration's
``graph_seed``; the run's ``--seed`` only permutes the order of the lap's
edges, so every seed offers the same multiset of edges and the same work, in
another order.
"""
from __future__ import annotations

import numpy as np


def zipf_cdf(n: int, alpha: float) -> np.ndarray:
    ranks = np.arange(1, n + 1, dtype=np.float64)
    cdf = np.cumsum(ranks ** (-alpha))
    return cdf / cdf[-1]


class GraphBatches:
    """Batch ``i`` of the configuration's graph: ``(src, dst, weight)``.

    Same arithmetic as the program's generator: a Philox stream keyed by
    ``(graph_seed << 20) + i + 1`` draws both endpoints by inverse CDF, self
    loops are moved to the next vertex, the last batch is zero-padded.
    """

    def __init__(self, graph: dict, batch_size: int) -> None:
        self.n_nodes = int(graph["n_nodes"])
        self.n_edges = int(graph["n_edges"])
        self.seed = int(graph["graph_seed"])
        self.batch_size = int(batch_size)
        self._cdf_src = zipf_cdf(self.n_nodes, float(graph["alpha_src"]))
        self._cdf_dst = zipf_cdf(self.n_nodes, float(graph["alpha_dst"]))
        perm_rng = np.random.default_rng(np.random.Philox(key=self.seed))
        self._perm_src = perm_rng.permutation(self.n_nodes).astype(np.int32)
        self._perm_dst = perm_rng.permutation(self.n_nodes).astype(np.int32)

    @property
    def num_batches(self) -> int:
        return -(-self.n_edges // self.batch_size)

    def batch_numpy(self, i: int):
        lo = i * self.batch_size
        n = min(self.batch_size, self.n_edges - lo)
        rng = np.random.default_rng(
            np.random.Philox(key=(self.seed << 20) + i + 1))
        u = rng.random((2, n))
        src = self._perm_src[np.searchsorted(self._cdf_src, u[0])]
        dst = self._perm_dst[np.searchsorted(self._cdf_dst, u[1])]
        collide = src == dst
        dst = np.where(collide, (dst + 1) % self.n_nodes, dst)
        weight = np.ones(n, np.int32)
        pad = self.batch_size - n
        if pad:
            src = np.concatenate([src, np.zeros(pad, np.int32)])
            dst = np.concatenate([dst, np.zeros(pad, np.int32)])
            weight = np.concatenate([weight, np.zeros(pad, np.int32)])
        return src.astype(np.int32), dst.astype(np.int32), weight


def graph_edges(graph: dict, batch_size: int):
    """Every edge of the graph in generator order, padding removed."""
    gen = GraphBatches(graph, batch_size)
    parts = [gen.batch_numpy(i) for i in range(gen.num_batches)]
    src = np.concatenate([p[0] for p in parts])
    dst = np.concatenate([p[1] for p in parts])
    w = np.concatenate([p[2] for p in parts])
    keep = w > 0
    return src[keep], dst[keep], w[keep]


class Lap:
    """One lap of the stream in the order drawn from ``seed``.

    Client batch ``k`` is the ``client_batch`` edges at lap positions
    ``[k*B, (k+1)*B)`` taken modulo the lap length, so batches run on across
    lap boundaries and every batch has exactly ``B`` edges.  The first ``E``
    submitted edges are ``E // L`` whole laps plus the lap's first ``E % L``.
    """

    def __init__(self, graph: dict, batch_size: int, seed: int,
                 client_batch: int) -> None:
        src, dst, w = graph_edges(graph, batch_size)
        order = np.random.default_rng(seed).permutation(src.shape[0])
        self.src = np.ascontiguousarray(src[order])
        self.dst = np.ascontiguousarray(dst[order])
        self.weight = np.ascontiguousarray(w[order])
        self.length = int(self.src.shape[0])
        self.client_batch = int(client_batch)

    def client_batch_numpy(self, k: int):
        b, n = self.client_batch, self.length
        lo = (k * b) % n
        idx = (lo + np.arange(b)) % n if lo + b > n else slice(lo, lo + b)
        return self.src[idx], self.dst[idx], self.weight[idx]

    def prefix(self, n_edges: int):
        """(laps, remainder) such that the first ``n_edges`` submitted edges
        are ``laps`` whole laps and then the lap's first ``remainder``."""
        return divmod(int(n_edges), self.length)
