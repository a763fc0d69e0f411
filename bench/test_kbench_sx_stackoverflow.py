"""The sx-stackoverflow-150m configuration: its reference copy counts only
the submitted prefix and agrees with ``references/kmatrix.py``; the
program's stream preset matches the configuration; the program's counters
on a scaled-down sx-stackoverflow equal the reference's; and the
``matrix_ingest_busy_share`` reader on recorded v5e trace excerpts."""
import json
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parent / "src")]

from kbench import discover, tiny  # noqa: E402
from kbench.stream import Lap  # noqa: E402
from kbench.trace import Event, TraceData  # noqa: E402

base = discover.reference(HERE, "kmatrix")
ref = discover.reference(HERE, "kmatrix_stream")
busy_share = discover.metric_reader(HERE, "matrix_ingest_busy_share")

CONFIG = json.loads((HERE / "configs" / "sx-stackoverflow-150m.json")
                    .read_text())
SCALE = 0.002  # of sx-stackoverflow: 5,203 nodes, 126,994 edges


def _scaled_config(budget_kb=128):
    g = dict(CONFIG["graph"], scale=SCALE,
             n_nodes=max(int(CONFIG["graph"]["n_nodes"] * SCALE), 16),
             n_edges=max(int(CONFIG["graph"]["n_edges"] * SCALE), 64))
    return dict(CONFIG, graph=g, sketch=dict(CONFIG["sketch"],
                                             budget_kb=budget_kb))


def _same(a: dict, b: dict) -> None:
    assert a.keys() == b.keys()
    for name in a:
        np.testing.assert_array_equal(a[name][0], b[name][0])
        np.testing.assert_array_equal(a[name][1], b[name][1])


@pytest.mark.parametrize("layout", ["pallas", "flat"])
@pytest.mark.parametrize("laps", [0.4, 1.0, 2.5])
def test_prefix_counters_equal_the_whole_lap_reference(layout, laps):
    lap = Lap(tiny.CONFIG["graph"], 8192, seed=2 ** 31 + 11,
              client_batch=2048)
    layout_ref = base.Layout(tiny.CONFIG, layout)
    n = int(round(laps * lap.length))
    chunk = 4_999  # several chunks a lap, and a ragged last one
    assert lap.length > 3 * chunk
    got = ref.counters_after(layout_ref, lap, n, chunk=chunk)
    _same(got, base.counters_after(layout_ref, lap, n))
    assert got["conn"][1].sum() == layout_ref.depth * n


def test_program_preset_matches_the_configuration():
    from repro.streams import DATASETS

    spec = DATASETS[CONFIG["graph"]["dataset"]]
    g = CONFIG["graph"]
    assert (spec.n_nodes, spec.n_edges, spec.alpha_src, spec.alpha_dst) == (
        g["n_nodes"], g["n_edges"], g["alpha_src"], g["alpha_dst"])


def _program_blocks(sketch):
    if hasattr(sketch, "pools"):
        out = {f"pool.{c}": np.asarray(p) for c, p in enumerate(sketch.pools)}
    else:
        out = {"pool.0": np.asarray(sketch.pool)}
    out["conn"] = np.asarray(sketch.conn)
    return out


@pytest.mark.parametrize("layout,batches", [("flat", 34), ("pallas", 3)])
def test_program_counters_equal_the_reference(layout, batches):
    """A scaled-down sx-stackoverflow through ``SketchRegistry.open`` at
    d = 7; the flat run goes past a lap boundary."""
    from repro.core.types import EdgeBatch
    from repro.serving.registry import SketchRegistry

    cfg = _scaled_config()
    g, sk = cfg["graph"], cfg["sketch"]
    reg = SketchRegistry(depth=sk["depth"], batch_size=8192,
                         sample_size=sk["sample_size"], scale=g["scale"],
                         partitioner=sk["partitioner"], sketch_backend=layout)
    tenant = reg.open(g["dataset"], "kmatrix", sk["budget_kb"], seed=0)
    spec = tenant.stream.spec
    assert (spec.n_nodes, spec.n_edges) == (g["n_nodes"], g["n_edges"])
    lap = Lap(g, 8192, seed=2 ** 33 + 7, client_batch=4096)
    n = 0
    for k in range(batches):
        src, dst, w = lap.client_batch_numpy(k)
        tenant.buffer.ingest(EdgeBatch.from_numpy(src, dst, w))
        n += len(src)
    snap = tenant.publish()
    assert snap.n_edges == n and (layout == "pallas" or n > lap.length)
    layout_ref = ref.Layout(cfg, layout)
    want = ref.counters_after(layout_ref, lap, n, chunk=50_000)
    got = _program_blocks(snap.sketch)
    assert ref.count_mismatches(layout_ref, got, want) == 0
    got["pool.0"] = got["pool.0"].copy()
    got["pool.0"].reshape(-1)[want["pool.0"][0][0]] += 1
    assert ref.count_mismatches(layout_ref, got, want) == 1


# ------------------------------------------------- matrix_ingest_busy_share

MS = 1e6  # ns
# op kinds as a result line's `breakdown` spells them (v5e traces of the 1 MB
# cells and the 1024 class)
BREAKDOWN_KERNELS = {"matrix_ingest_s32_7_14_16_16_": 16,
                     "matrix_ingest_s32_7_13_16_16_": 16,
                     "matrix_ingest_s32_7_2_32_32_": 32,
                     "matrix_ingest_s32_7_2_64_64_": 64,
                     "matrix_ingest_s32_7_1_128_128_": 128,
                     "matrix_ingest_s32_7_1_1024_1024_": 1024}
NOT_KERNELS = ["fusion_s32_4096_", "while__s32__", "fusion_s32_7_57344_",
               "%fusion.8 = s32[1490944]{0:T", "jit__ingest_counted",
               "%copy-done.16 = s32[6144]{0:T"]


def test_pattern_pins_the_kernel_names():
    import re

    rx = re.compile(busy_share.PATTERN)
    for name, w in BREAKDOWN_KERNELS.items():
        assert rx.search(name), name
        assert busy_share.width_class(name) == w
    assert rx.search("%matrix_ingest.5 = s32[7,2,512,512]{3,2,1,0:T")
    assert busy_share.width_class(
        "%matrix_ingest.5 = s32[7,2,512,512]{3,2,1,0:T") == 512
    for name in NOT_KERNELS:
        assert not rx.search(name), name


def test_busy_share_on_a_toy_trace():
    ops, dev = "XLA Ops", "/device:TPU:0"
    device = [
        Event("jit__ingest_counted(1)", 0, 10 * MS, "XLA Modules", dev),
        Event("%fusion.1 = s32[6144]{0:T", 0, 1 * MS, ops, dev),
        Event("%while.4 = ", 1 * MS, 5 * MS, ops, dev),
        # nested in the while: counted once in busy time
        Event("%matrix_ingest.3 = s32[7,15,256,256]{3,2,1,0:T", 1 * MS,
              4 * MS, ops, dev),
        Event("%matrix_ingest.5 = s32[7,1,1024,1024]{3,2,1,0:T", 6 * MS,
              9 * MS, ops, dev),
        # runs past the window's end: only its part inside counts
        Event("%matrix_ingest.5 = s32[7,1,1024,1024]{3,2,1,0:T", 11 * MS,
              14 * MS, ops, dev),
    ]
    tr = TraceData(device, [], 0.0, 12 * MS)
    # busy 0-5, 6-9, 11-12 = 9 ms; kernel 1-4, 6-9, 11-12 = 7 ms
    assert busy_share.read(SimpleNamespace(trace=tr)) == \
        pytest.approx(100 * 7 / 9)
    assert busy_share.by_class_s(tr) == pytest.approx({256: 3e-3,
                                                       1024: 4e-3})
    no_kernel = TraceData(device[:3], [], 0.0, 12 * MS)
    assert busy_share.read(SimpleNamespace(trace=no_kernel)) is None
    assert busy_share.read(SimpleNamespace(trace=None)) is None


EXCERPTS = {"trace_excerpt_cit_ingest.json": {128, 256, 512},
            "trace_excerpt_email_ingest.json": {4096}}


@pytest.mark.parametrize("name", sorted(EXCERPTS))
def test_busy_share_on_recorded_chip_traces(name):
    """Slices of v5e traces of ingest cells (read only): the kernel's ops
    are found by the pattern and split by the classes they update."""
    tr = TraceData.from_json((HERE / "testdata" / name).read_text())
    share = busy_share.read(SimpleNamespace(trace=tr))
    assert 0 < share < 100
    kernels = [e for e in tr.line("XLA Ops")
               if e.name.startswith("%matrix_ingest")]
    assert kernels
    assert set(busy_share.by_class_s(tr)) == EXCERPTS[name]
    kernel_s = sum(busy_share.by_class_s(tr).values())
    assert share == pytest.approx(100 * kernel_s / tr.busy_s(), rel=1e-9)
