"""Query traffic: a copy of the program's ``synth_requests`` (Zipf-skewed
endpoints over the node universe) and the open-loop schedule of
``OpenLoopLoadGen``, kept here so that a change to the program's defaults
cannot move the yardstick.  Requests are built as the program's own
``Request`` objects, since that is the form the engine takes them in.
"""
from __future__ import annotations

import numpy as np

FAMILIES = ("edge_freq", "reach", "node_out", "path_weight",
            "subgraph_weight", "heavy_nodes")


def synth_requests(n: int, mix: dict, *, n_nodes: int, seed: int,
                   zipf_a: float, path_len: int, subgraph_edges: int,
                   heavy_universe: int, heavy_threshold: float) -> list:
    """Draw ``n`` requests; family by ``mix`` weight, endpoints Zipf(a)."""
    from repro.serving import engine as eng

    rng = np.random.default_rng(seed)
    fams = [f for f in FAMILIES if mix.get(f, 0) > 0]
    unknown = set(mix) - set(FAMILIES)
    if unknown:
        raise ValueError(f"unknown query families in the mix: {unknown}")
    p = np.asarray([float(mix[f]) for f in fams])
    choice = rng.choice(len(fams), size=n, p=p / p.sum())

    def node() -> int:
        return int(min(rng.zipf(zipf_a) - 1, n_nodes - 1))

    reqs = []
    for c in choice:
        fam = fams[c]
        if fam == "edge_freq":
            reqs.append(eng.edge_freq(node(), node()))
        elif fam == "reach":
            reqs.append(eng.reach(node(), node()))
        elif fam == "node_out":
            reqs.append(eng.node_out(node()))
        elif fam == "path_weight":
            reqs.append(eng.path_weight([node() for _ in range(path_len)]))
        elif fam == "subgraph_weight":
            reqs.append(eng.subgraph_weight(
                [(node(), node()) for _ in range(subgraph_edges)]))
        else:
            reqs.append(eng.heavy_nodes(heavy_universe, heavy_threshold))
    return reqs


def engine_shapes(requests: list, batch_max: int) -> list:
    """One batch per shape the engine can compile for this mix: for each
    family present, a batch of every power of two up to ``batch_max``
    requests (the engine pads each family group to a power-of-two bucket),
    built from that family's own requests."""
    by_family: dict = {}
    for r in requests:
        by_family.setdefault(r.family, []).append(r)
    batches = []
    for fam, reqs in by_family.items():
        size = 1
        while True:
            n = min(size, batch_max)
            batches.append([reqs[i % len(reqs)] for i in range(n)])
            if n >= batch_max:
                break
            size *= 2
    return batches
