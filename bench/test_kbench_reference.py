"""The plain reference against the program on a tiny stream: counters of
both layouts and the answers of every query family, over a lap boundary."""
import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parent / "src")]

from kbench import discover, tiny  # noqa: E402
from kbench.stream import Lap  # noqa: E402

ref = discover.reference(HERE, "kmatrix")


def _tenant(layout):
    from repro.serving.registry import SketchRegistry

    sk = tiny.CONFIG["sketch"]
    g = tiny.CONFIG["graph"]
    reg = SketchRegistry(depth=sk["depth"], batch_size=8192,
                         sample_size=sk["sample_size"], scale=g["scale"],
                         partitioner="banded", sketch_backend=layout)
    return reg.open(g["dataset"], "kmatrix", sk["budget_kb"], seed=0)


def _program_blocks(sketch):
    if hasattr(sketch, "pools"):
        out = {f"pool.{c}": np.asarray(p) for c, p in enumerate(sketch.pools)}
    else:
        out = {"pool.0": np.asarray(sketch.pool)}
    out["conn"] = np.asarray(sketch.conn)
    return out


@pytest.mark.parametrize("layout,batches", [("flat", 13), ("pallas", 3)])
def test_counters_match_the_program(layout, batches):
    from repro.core.types import EdgeBatch

    tenant = _tenant(layout)
    lap = Lap(tiny.CONFIG["graph"], 8192, seed=2 ** 33 + 5,
              client_batch=2048)
    n = 0
    for k in range(batches):
        src, dst, w = lap.client_batch_numpy(k)
        tenant.buffer.ingest(EdgeBatch.from_numpy(src, dst, w))
        n += len(src)
    snap = tenant.publish()
    assert snap.n_edges == n
    layout_ref = ref.Layout(tiny.CONFIG, layout)
    want = ref.counters_after(layout_ref, lap, n)
    got = _program_blocks(snap.sketch)
    assert ref.count_mismatches(layout_ref, got, want) == 0
    # one cell off is one mismatch; a missing block counts all its cells
    got["conn"] = got["conn"].copy()
    got["conn"][0, 0, 0] += 1
    assert ref.count_mismatches(layout_ref, got, want) == 1
    del got["conn"]
    assert ref.count_mismatches(layout_ref, got, want) == int(
        np.prod(layout_ref.blocks["conn"]))


def test_answers_match_the_engine_after_a_lap():
    from repro.core.types import EdgeBatch
    from repro.serving import engine as eng

    tenant = _tenant("flat")
    lap = Lap(tiny.CONFIG["graph"], 8192, seed=99, client_batch=2048)
    n = 0
    for k in range(12):  # 24,576 edges: one lap of 21,078 and a partial
        src, dst, w = lap.client_batch_numpy(k)
        tenant.buffer.ingest(EdgeBatch.from_numpy(src, dst, w))
        n += len(src)
    snap = tenant.publish()
    assert n > lap.length
    rng = np.random.default_rng(0)
    v = lap.src[rng.integers(0, lap.length, 40)]
    u = lap.dst[rng.integers(0, lap.length, 40)]
    reqs = ([eng.edge_freq(int(a), int(b)) for a, b in zip(v, u)]
            + [eng.node_out(int(a)) for a in v[:20]]
            + [eng.reach(int(a), int(b)) for a, b in zip(v[:20], u[20:])]
            + [eng.reach(5, 5), eng.heavy_nodes(600, 20.0),
               eng.path_weight([int(x) for x in v[:4]]),
               eng.subgraph_weight([(int(a), int(b))
                                    for a, b in zip(v[:3], u[:3])])])
    got = [r.value for r in eng.QueryEngine().execute(snap, reqs)]
    answers = ref.Answers(ref.Layout(tiny.CONFIG, "flat"), lap)
    for r, g in zip(reqs, got):
        assert ref.same_answer(g, answers.answer(r, n)), r
    heavy = answers.answer(reqs[-3], n)
    assert heavy[0].size > 0
    # an answer one off is told apart
    assert not ref.same_answer(got[0] + 1, answers.answer(reqs[0], n))


def test_prefix_counts_follow_laps():
    keys = np.array([[3, 1, 3, 2]])
    pc = ref.PrefixCounts(keys, np.array([1, 1, 1, 1]))
    # after one lap and two more edges: key 3 seen 2 + 1 times
    assert pc.count(0, np.array([3, 1, 2]), 1, 2).tolist() == [3, 2, 1]
    assert pc.count(0, np.array([7]), 5, 3).tolist() == [0]
