"""Plain reference of the kMatrix sketch (arXiv:2105.05503, section IV).

Imports nothing of the program under test and takes nothing it made.  From
the configuration alone it derives what fixes the sketch: the bootstrap
reservoir sample of the graph, the per-vertex statistics, the banded
partition plan (sqrt-G area allocation over equal-count frequency bands, the
repo's documented partitioner), the global connectivity width and the
multiply-shift hash family.  It then applies the update rule

    pool[l][p(s)][h_l(s) -> w_p][h_l(d) -> w_p] += weight
    conn[l][h_l(s) -> cw][h_l(d) -> cw]         += weight

to the submitted edges in numpy, and answers every query family the serving
engine plans (edge frequency, node out-frequency, reachability, path and
subgraph weight, heavy nodes) directly from the cells.

The physical layout only permutes cells.  ``layout="pallas"`` (width-class
pools: every partition width rounded down to a power of two, pools grouped
by width) and ``layout="flat"`` (one pool of concatenated ``w_p x w_p``
slabs) are both described, so the comparison holds whichever layout the
platform default picks.
"""
from __future__ import annotations

import numpy as np

from kbench.stream import GraphBatches

_MIX_MUL = np.uint32(0x7FEB352D)


# ------------------------------------------------------------------ hashing

def hash_params(seed: int, depth: int):
    rng = np.random.default_rng(seed)
    a = rng.integers(0, 1 << 32, size=depth, dtype=np.uint32) | np.uint32(1)
    b = rng.integers(0, 1 << 32, size=depth, dtype=np.uint32)
    return a, b


def mix(a: np.ndarray, b: np.ndarray, x: np.ndarray) -> np.ndarray:
    """uint32[d, n]: multiply-add, then one xorshift-multiply round."""
    x = np.asarray(x).astype(np.uint32)
    with np.errstate(over="ignore"):
        h = a[:, None] * x[None, :] + b[:, None]
        h = h ^ (h >> np.uint32(16))
        h = h * _MIX_MUL
        h = h ^ (h >> np.uint32(15))
    return h


def fastrange(h: np.ndarray, w) -> np.ndarray:
    """Map uint32 hashes onto [0, w) by the high half of h * w."""
    return ((h.astype(np.uint64) * np.asarray(w, np.uint64))
            >> np.uint64(32)).astype(np.int64)


# -------------------------------------------------------- bootstrap sample

def reservoir_sample(gen: GraphBatches, k: int, seed: int):
    """Algorithm R over the graph's batches in order; vectorized, with the
    same draws as the one-at-a-time loop (last accepted item per slot wins)."""
    rng = np.random.default_rng(np.random.Philox(key=seed ^ 0x5EED))
    s_buf = np.zeros(k, np.int32)
    d_buf = np.zeros(k, np.int32)
    w_buf = np.zeros(k, np.int32)
    seen = 0
    for i in range(gen.num_batches):
        src, dst, w = gen.batch_numpy(i)
        live = w > 0
        src, dst, w = src[live], dst[live], w[live]
        if seen < k:
            take = min(k - seen, src.shape[0])
            s_buf[seen:seen + take] = src[:take]
            d_buf[seen:seen + take] = dst[:take]
            w_buf[seen:seen + take] = w[:take]
            seen += take
            src, dst, w = src[take:], dst[take:], w[take:]
        n = src.shape[0]
        if n == 0:
            continue
        t = seen + np.arange(1, n + 1, dtype=np.float64)
        accept = rng.random(n) < (k / t)
        slots = rng.integers(0, k, size=n)
        idx = np.nonzero(accept)[0]
        if idx.size:
            acc = slots[idx]
            uniq, last_rev = np.unique(acc[::-1], return_index=True)
            win = idx[idx.size - 1 - last_rev]
            s_buf[uniq], d_buf[uniq], w_buf[uniq] = src[win], dst[win], w[win]
        seen += n
    n = min(seen, k)
    return s_buf[:n], d_buf[:n], w_buf[:n]


def vertex_stats(src, dst, w):
    """(vertex, freq, deg) per sampled source; freq and deg in float32, as
    the sketch's statistics record them."""
    order = np.argsort(src, kind="stable")
    s, d, ww = src[order], dst[order], w[order]
    verts, starts = np.unique(s, return_index=True)
    ends = np.append(starts[1:], len(s))
    freq = np.add.reduceat(ww.astype(np.int64), starts).astype(np.float32)
    deg = np.array([len(np.unique(d[lo:hi])) for lo, hi in zip(starts, ends)],
                   np.float32)
    return verts.astype(np.int32), freq, deg


# ------------------------------------------------------------ partitioning

def outlier_share(freq: np.ndarray) -> float:
    """Good-Turing share of stream edges whose source the sample never saw."""
    n = float(freq.sum())
    if n <= 0:
        return 0.5
    return float(np.clip(float((freq <= 1.0).sum()) / n, 0.05, 0.6))


def banded_plan(verts, freq, deg, total_width: int, n_bands: int = 16,
                min_width: int = 8):
    """Banded partitioner: equal-count bands of average edge frequency, areas
    allocated by sqrt(G), integer remainder spent widening the narrowest.

    Returns (route_keys sorted, route_part, widths incl. outlier last)."""
    freq = freq.astype(np.float64)
    deg = deg.astype(np.float64)
    out_frac = outlier_share(freq)
    order = np.argsort(freq / np.maximum(deg, 1.0), kind="stable")
    v, f, dg = verts[order], freq[order], deg[order]
    bounds = np.linspace(0, len(v), n_bands + 1).astype(int)
    groups, gs = [], []
    for i in range(n_bands):
        lo, hi = bounds[i], bounds[i + 1]
        if hi <= lo:
            continue
        g = f[lo:hi].sum() * float((dg[lo:hi] ** 2
                                    / np.maximum(f[lo:hi], 1e-9)).sum())
        groups.append((lo, hi))
        gs.append(max(g, 1e-9))
    gs = np.asarray(gs)
    area = float(total_width) ** 2
    out_area = area * out_frac
    alloc = (area - out_area) * np.sqrt(gs) / np.sqrt(gs).sum()
    widths = np.maximum(np.sqrt(alloc).astype(np.int64), min_width)
    all_w = np.concatenate([widths, [max(int(np.sqrt(out_area)), min_width)]])
    grown = True
    while grown:
        grown = False
        for i in np.argsort(all_w):
            if int((all_w ** 2).sum()) + 2 * int(all_w[i]) + 1 <= area:
                all_w[i] += 1
                grown = True
    keys = np.concatenate([v[lo:hi] for lo, hi in groups]).astype(np.int32)
    part = np.concatenate([np.full(hi - lo, i, np.int32)
                           for i, (lo, hi) in enumerate(groups)])
    o = np.argsort(keys, kind="stable")
    return keys[o], part[o], all_w.astype(np.int64)


# ------------------------------------------------------------------ layout

class Layout:
    """Where every cell of the sketch lives, derived from the configuration.

    ``blocks`` names the program's counter arrays and their shapes:
    ``pool.<c>`` of shape [d, P_c, w_c, w_c] per width class ``c`` (pallas)
    or one ``pool.0`` of shape [d, sum w_p^2] (flat), and ``conn``
    [d, cw, cw].
    """

    def __init__(self, cfg: dict, layout: str) -> None:
        graph, sk = cfg["graph"], cfg["sketch"]
        self.depth = d = int(sk["depth"])
        self.seed = int(graph["graph_seed"])
        counters = int(sk["budget_kb"]) * 1024 // 4
        per_layer = max(counters // d, 4)
        self.conn_w = int(np.sqrt(per_layer * float(sk["conn_frac"])))
        total_width = max(int(np.sqrt(per_layer - self.conn_w ** 2)), 2)
        gen = GraphBatches(graph, int(sk["registry_batch_size"]))
        n_sample = max(int(int(sk["sample_size"]) * float(graph["scale"])),
                       1000)
        verts, freq, deg = vertex_stats(
            *reservoir_sample(gen, n_sample, self.seed + 1))
        self.keys, self.part, widths = banded_plan(
            verts, freq, deg, total_width, n_bands=int(sk["n_bands"]),
            min_width=int(sk["min_width"]))
        self.outlier = len(widths) - 1
        if layout == "pallas":
            widths = np.asarray([1 << (int(w).bit_length() - 1)
                                 for w in widths], np.int64)
        elif layout != "flat":
            raise ValueError(f"unknown sketch layout {layout!r}")
        self.layout = layout
        self.widths = widths
        self.a, self.b = hash_params(self.seed, d)
        P = len(widths)
        if layout == "pallas":
            classes = sorted(set(int(w) for w in widths))
            self.part_block = np.asarray(
                [classes.index(int(w)) for w in widths], np.int64)
            self.part_row = np.zeros(P, np.int64)
            self.blocks = {}
            self.class_rows = np.zeros(len(classes), np.int64)
            for c, w_c in enumerate(classes):
                members = np.nonzero(self.part_block == c)[0]
                self.part_row[members] = np.arange(len(members))
                self.class_rows[c] = len(members)
                self.blocks[f"pool.{c}"] = (d, len(members), w_c, w_c)
            self.part_base = np.zeros(P, np.int64)
        else:
            slab = widths ** 2
            self.part_block = np.zeros(P, np.int64)
            self.part_row = np.zeros(P, np.int64)
            self.part_base = np.concatenate([[0], np.cumsum(slab)[:-1]])
            self.blocks = {"pool.0": (d, int(slab.sum()))}
        self.blocks["conn"] = (d, self.conn_w, self.conn_w)

    # -- per-edge cell addresses ------------------------------------------
    def route(self, v: np.ndarray) -> np.ndarray:
        v = np.asarray(v, np.int64)
        if self.keys.size == 0:
            return np.full(v.shape, self.outlier, np.int64)
        pos = np.clip(np.searchsorted(self.keys, v), 0, self.keys.size - 1)
        return np.where(self.keys[pos] == v, self.part[pos],
                        self.outlier).astype(np.int64)

    def pool_cells(self, src, dst):
        """(block index, flat cell index within the block) per layer and
        edge, both int64[d, n]."""
        p = self.route(src)
        w = self.widths[p]
        hi = fastrange(mix(self.a, self.b, src), w[None])
        hj = fastrange(mix(self.a, self.b, dst), w[None])
        block = np.broadcast_to(self.part_block[p][None], hi.shape)
        if self.layout == "pallas":
            layer = np.arange(self.depth)[:, None]
            cell = ((layer * self.class_rows[block] + self.part_row[p][None])
                    * w[None]
                    + hi) * w[None] + hj
        else:
            cell = (np.arange(self.depth)[:, None]
                    * self.blocks["pool.0"][1]
                    + self.part_base[p][None] + hi * w[None] + hj)
        return block, cell

    def pool_rows(self, v):
        """A key per layer naming v's row in its partition: int64[d, n]."""
        p = self.route(v)
        hi = fastrange(mix(self.a, self.b, v), self.widths[p][None])
        return (np.arange(self.depth)[:, None] * (self.outlier + 1)
                + p[None]) * int(self.widths.max()) + hi

    def conn_slots(self, v):
        return fastrange(mix(self.a, self.b, v), self.conn_w)


# ---------------------------------------------------------- counter state

def _cells(layout: Layout, src, dst) -> dict:
    """Per block, the flat cell index each (layer, edge) update lands in,
    and which edge it came from."""
    block, cell = layout.pool_cells(src, dst)
    edge = np.broadcast_to(np.arange(src.shape[0])[None], cell.shape)
    out = {}
    for name in layout.blocks:
        if name == "conn":
            continue
        sel = block == int(name.split(".")[1])
        out[name] = (cell[sel], edge[sel])
    cw = layout.conn_w
    ci, cj = layout.conn_slots(src), layout.conn_slots(dst)
    flat = (np.arange(layout.depth)[:, None] * cw + ci) * cw + cj
    out["conn"] = (flat.ravel(), np.broadcast_to(
        np.arange(src.shape[0])[None], flat.shape).ravel())
    return out


def counters_after(layout: Layout, lap, n_edges: int) -> dict:
    """The counters after the first ``n_edges`` submitted edges, sparse:
    per block, sorted cell indices and their int64 values (every other cell
    is zero)."""
    laps, rem = lap.prefix(n_edges)
    w = lap.weight.astype(np.int64)
    scale = np.where(np.arange(lap.length) < rem, laps + 1, laps)
    out = {}
    for name, (cell, edge) in _cells(layout, lap.src, lap.dst).items():
        wts = (w * scale)[edge]
        keep = wts != 0
        idx, inv = np.unique(cell[keep], return_inverse=True)
        val = np.bincount(inv, weights=wts[keep], minlength=idx.size)
        out[name] = (idx, np.rint(val).astype(np.int64))
    return out


def count_mismatches(layout: Layout, program: dict, reference: dict) -> int:
    """Cells of the program's counters that differ from the reference's.

    ``program`` maps block names to host arrays in the program's own shape;
    a block whose shape differs from the reference layout counts all its
    cells as wrong."""
    wrong = 0
    for name, shape in layout.blocks.items():
        got = program.get(name)
        size = int(np.prod(shape))
        if got is None or tuple(got.shape) != tuple(shape):
            wrong += size
            continue
        flat = got.reshape(-1)
        idx, val = reference[name]
        at = flat[idx].astype(np.int64)
        # wrong where the reference has a count, plus any nonzero elsewhere
        wrong += int(np.count_nonzero(at != val))
        wrong += int(np.count_nonzero(flat)) - int(np.count_nonzero(at))
    extra = set(program) - set(layout.blocks)
    return wrong + sum(int(program[k].size) for k in extra)


# ------------------------------------------------------------ query answers

class PrefixCounts:
    """Weighted counts of per-edge keys over any prefix of the stream.

    For one layer's keys ``k[i]`` of lap edge ``i``, the count of key ``c``
    after ``n`` submitted edges is ``laps * total(c) + prefix(c, rem)``: both
    come from one sort of ``c * L + i`` and a cumulative weight sum."""

    def __init__(self, keys: np.ndarray, weight: np.ndarray) -> None:
        L = keys.shape[-1]
        self.L = L
        comp = keys.astype(np.int64) * L + np.arange(L)[None]
        order = np.argsort(comp, axis=1)
        self.comp = np.take_along_axis(comp, order, axis=1)
        w = np.asarray(weight, np.int64)[order]
        self.cum = np.concatenate(
            [np.zeros((keys.shape[0], 1), np.int64), np.cumsum(w, axis=1)],
            axis=1)

    def count(self, layer: int, keys: np.ndarray, laps: int, rem: int):
        keys = np.asarray(keys, np.int64)
        c, cum = self.comp[layer], self.cum[layer]
        lo = np.searchsorted(c, keys * self.L)
        end = np.searchsorted(c, keys * self.L + self.L)
        mid = np.searchsorted(c, keys * self.L + rem)
        return laps * (cum[end] - cum[lo]) + (cum[mid] - cum[lo])


class Answers:
    """Direct answers of every query family at any point of the stream."""

    def __init__(self, layout: Layout, lap) -> None:
        self.layout = layout
        self.lap = lap
        block, cell = layout.pool_cells(lap.src, lap.dst)
        # pool cells of different blocks never share a key: tag the block
        total = max(int(np.prod(s)) for s in layout.blocks.values())
        self.cells = PrefixCounts(block * total + cell, lap.weight)
        self.rows = PrefixCounts(layout.pool_rows(lap.src), lap.weight)
        cw = layout.conn_w
        self.conn_keys = layout.conn_slots(lap.src) * cw \
            + layout.conn_slots(lap.dst)
        self._cell_total = total
        self._adj_cache: dict = {}

    def edge_freq(self, src, dst, n_edges: int) -> np.ndarray:
        laps, rem = self.lap.prefix(n_edges)
        src = np.atleast_1d(np.asarray(src, np.int64))
        dst = np.atleast_1d(np.asarray(dst, np.int64))
        block, cell = self.layout.pool_cells(src, dst)
        keys = block * self._cell_total + cell
        per_layer = [self.cells.count(l, keys[l], laps, rem)
                     for l in range(self.layout.depth)]
        return np.min(np.stack(per_layer), axis=0)

    def node_out(self, v, n_edges: int) -> np.ndarray:
        laps, rem = self.lap.prefix(n_edges)
        v = np.atleast_1d(np.asarray(v, np.int64))
        rows = self.layout.pool_rows(v)
        per_layer = [self.rows.count(l, rows[l], laps, rem)
                     for l in range(self.layout.depth)]
        return np.min(np.stack(per_layer), axis=0)

    def _adjacency(self, n_edges: int):
        """Per-layer boolean adjacency of the conn matrix at ``n_edges``:
        a cell is an arc once any edge with positive weight reached it."""
        laps, rem = self.lap.prefix(n_edges)
        key = min(laps, 1), rem if laps == 0 else -1
        adj = self._adj_cache.get(key)
        if adj is None:
            cw = self.layout.conn_w
            upto = self.lap.length if laps else rem
            adj = []
            for l in range(self.layout.depth):
                k = self.conn_keys[l, :upto][self.lap.weight[:upto] > 0]
                m = np.zeros(cw * cw, bool)
                m[k] = True
                adj.append(m.reshape(cw, cw))
            if len(self._adj_cache) > 4:
                self._adj_cache.clear()
            self._adj_cache[key] = adj
        return adj

    def reach(self, src: int, dst: int, n_edges: int) -> bool:
        """True when every layer's conn graph has a path src -> dst (a
        vertex always reaches itself)."""
        adj = self._adjacency(n_edges)
        hs = self.layout.conn_slots(np.asarray([src]))[:, 0]
        hd = self.layout.conn_slots(np.asarray([dst]))[:, 0]
        for l in range(self.layout.depth):
            if not _reaches(adj[l], int(hs[l]), int(hd[l])):
                return False
        return True

    def heavy_nodes(self, universe: int, threshold: float, n_edges: int):
        ids = np.arange(universe, dtype=np.int64)
        freqs = self.node_out(ids, n_edges)
        keep = freqs >= threshold
        return ids[keep], freqs[keep]

    def answer(self, request, n_edges: int):
        """The direct answer to one engine request (duck-typed: ``family``
        and the fields its family uses)."""
        fam = request.family
        if fam == "edge_freq":
            return int(self.edge_freq([request.src], [request.dst],
                                      n_edges)[0])
        if fam == "node_out":
            return int(self.node_out([request.node], n_edges)[0])
        if fam == "reach":
            if request.max_hops is not None:
                raise ValueError("bounded-hop reachability is not in the mix")
            return self.reach(request.src, request.dst, n_edges)
        if fam == "path_weight":
            nodes = np.asarray(request.nodes, np.int64)
            return int(self.edge_freq(nodes[:-1], nodes[1:], n_edges).sum())
        if fam == "subgraph_weight":
            e = np.asarray(request.edges, np.int64).reshape(-1, 2)
            return int(self.edge_freq(e[:, 0], e[:, 1], n_edges).sum())
        if fam == "heavy_nodes":
            return self.heavy_nodes(request.universe, request.threshold,
                                    n_edges)
        raise ValueError(f"no reference answer for family {fam!r}")


def _reaches(adj: np.ndarray, s: int, t: int) -> bool:
    if s == t:
        return True
    seen = np.zeros(adj.shape[0], bool)
    seen[s] = True
    frontier = np.asarray([s])
    while frontier.size:
        nxt = adj[frontier].any(axis=0) & ~seen
        if nxt[t]:
            return True
        seen |= nxt
        frontier = np.nonzero(nxt)[0]
    return False


def same_answer(got, want) -> bool:
    """Exact equality of an engine value and a reference answer."""
    if isinstance(want, tuple):
        ids, freqs = got
        return (np.array_equal(np.asarray(ids, np.int64), want[0])
                and np.array_equal(np.asarray(freqs, np.int64), want[1]))
    if isinstance(want, bool):
        return bool(got) == want and isinstance(got, (bool, np.bool_))
    return int(got) == int(want)
