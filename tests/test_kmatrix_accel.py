"""Width-class sketch backend seams: protocol parity with the flat-pool
kMatrix, bit-exact relayout, merge rejection rules, checkpoint round-trips
and backend resolution (ISSUE 3 tentpole coverage)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.checkpoint import store
from repro.core import (
    EdgeBatch,
    KMatrix,
    KMatrixAccel,
    queries,
    sketch_backend,
    vertex_stats_from_sample,
)
from repro.core import kmatrix, kmatrix_accel as kma


def _random_stream(seed, n=4096, nodes=2000):
    rng = np.random.default_rng(seed)
    src = rng.zipf(1.3, n).astype(np.int32) % nodes
    dst = rng.integers(0, nodes, n).astype(np.int32)
    w = rng.integers(1, 4, n).astype(np.int32)
    return src, dst, w


def _accel(seed=1, sample_seed=0, depth=3, budget=1 << 16):
    src, dst, w = _random_stream(sample_seed)
    stats = vertex_stats_from_sample(src[:1000], dst[:1000], w[:1000])
    return KMatrixAccel.create(bytes_budget=budget, stats=stats, depth=depth,
                               seed=seed)


def _leaves_equal(a, b) -> bool:
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(la, lb))


# ------------------------------------------------------------ flat parity --
def test_accel_vs_flat_bit_exact_on_randomized_streams():
    """Accel ingest == flat ingest on the SAME quantized layout: counters,
    edge_freq and node_out_freq all bit-identical, for several streams."""
    acc0 = _accel(seed=5)
    flat0 = kma.to_flat_layout(acc0)
    for seed in (1, 2, 3):
        src, dst, w = _random_stream(100 + seed)
        batch = EdgeBatch.from_numpy(src, dst, w)
        # tiny capacity forces a large overflow tail through the scatter path
        acc = kma.ingest(acc0, batch, capacity=128, block_b=128)
        flat = kmatrix.ingest(flat0, batch)
        np.testing.assert_array_equal(
            np.asarray(kma.to_flat_layout(acc).pool), np.asarray(flat.pool))
        q, qd = jnp.asarray(src[:512]), jnp.asarray(dst[:512])
        np.testing.assert_array_equal(
            np.asarray(kma.edge_freq(acc, q, qd)),
            np.asarray(kmatrix.edge_freq(flat, q, qd)))
        np.testing.assert_array_equal(
            np.asarray(kma.node_out_freq(acc, q)),
            np.asarray(kmatrix.node_out_freq(flat, q)))


def test_accel_reachability_matches_flat():
    acc = _accel(seed=2)
    src, dst, w = _random_stream(7, n=1024, nodes=300)
    batch = EdgeBatch.from_numpy(src, dst, w)
    acc = kma.ingest(acc, batch)
    flat = kma.to_flat_layout(acc)
    qs, qd = jnp.asarray(src[:64]), jnp.asarray(dst[::-1][:64])
    np.testing.assert_array_equal(
        np.asarray(queries.closure_layers(acc)),
        np.asarray(queries.closure_layers(flat)))
    np.testing.assert_array_equal(
        np.asarray(queries.reach_cells(acc, qs)),
        np.asarray(queries.reach_cells(flat, qs)))
    closure = queries.build_closure(queries.closure_layers(acc))
    a = queries.reachability_from_closure(
        closure, queries.reach_cells(acc, qs), queries.reach_cells(acc, qd))
    b = queries.reachability_from_closure(
        closure, queries.reach_cells(flat, qs), queries.reach_cells(flat, qd))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# --------------------------------------------------------------- relayout --
def test_relayout_roundtrip_is_identity():
    """to_class_layout ∘ to_flat_layout == id on every pytree leaf —
    INCLUDING the overflow tally (ISSUE 4 satellite: a relayout round-trip
    or flat-checkpoint migration must not zero a nonzero diagnostic)."""
    acc = _accel(seed=3)
    src, dst, w = _random_stream(11)
    acc = kma.ingest(acc, EdgeBatch.from_numpy(src, dst, w),
                     capacity=128, block_b=128)
    assert int(acc.overflow) > 0, "round-trip must carry a real tally"
    flat = kma.to_flat_layout(acc)
    assert int(flat.overflow) == int(acc.overflow)
    back = kma.to_class_layout(flat)
    assert back.class_widths == acc.class_widths
    assert back.class_counts == acc.class_counts
    assert back.conn_w == acc.conn_w
    assert _leaves_equal(back, acc)
    assert int(back.overflow) == int(acc.overflow)
    # an explicit override still wins (checkpoint-migration escape hatch)
    assert int(kma.to_class_layout(flat, overflow=0).overflow) == 0


def test_flat_overflow_leaf_is_inert_and_additive():
    """The flat KMatrix carries the diagnostic but never writes it: ingest
    leaves it unchanged, empty_like zeroes it, merge sums it."""
    acc = _accel(seed=3)
    src, dst, w = _random_stream(12)
    acc = kma.ingest(acc, EdgeBatch.from_numpy(src, dst, w),
                     capacity=128, block_b=128)
    flat = kma.to_flat_layout(acc)
    tally = int(flat.overflow)
    assert tally > 0
    flat2 = kmatrix.ingest(flat, EdgeBatch.from_numpy(src, dst, w))
    assert int(flat2.overflow) == tally, "flat ingest must not touch it"
    assert int(kmatrix.empty_like(flat).overflow) == 0
    assert int(kmatrix.merge(flat, flat2).overflow) == 2 * tally


def test_dispatch_capacity_sized_from_plan_load():
    """ISSUE 4 satellite: default dispatch capacity comes from the
    partition plan's banded load (max per-partition share, 2x headroom,
    capped at B), not the uniform 2B/P — and capacity is a dispatch-only
    concern: counters are bit-identical under any capacity."""
    src, dst, w = _random_stream(0)
    stats = vertex_stats_from_sample(src[:1000], dst[:1000], w[:1000])
    acc = KMatrixAccel.create(bytes_budget=1 << 16, stats=stats, depth=3,
                              seed=1, partitioner="banded")
    assert acc.load_shares is not None
    assert len(acc.load_shares) == acc.route.n_partitions
    assert 0.99 <= sum(acc.load_shares) <= 1.01
    b = 4096
    cap = kma.dispatch_capacity(acc, b)
    want = int(np.ceil(2.0 * max(acc.load_shares) * b))
    assert cap >= min(want, b) and cap % 128 == 0 and cap <= b + 127
    # relayouted sketches carry no sample: uniform fallback
    relayout = kma.to_class_layout(kma.to_flat_layout(acc))
    assert relayout.load_shares is None
    legacy = kma.dispatch_capacity(relayout, b)
    assert legacy == -(-max(128, (2 * b) // acc.route.n_partitions)
                       // 128) * 128
    # capacity never changes counters, only the MXU/scatter split
    batch = EdgeBatch.from_numpy(*_random_stream(55))
    a = kma.ingest(acc, batch)                    # plan-derived default
    bb = kma.ingest(acc, batch, capacity=legacy)  # legacy uniform
    assert _leaves_equal(a.pools, bb.pools)
    np.testing.assert_array_equal(np.asarray(a.conn), np.asarray(bb.conn))


def test_to_class_layout_rejects_unquantized_plan():
    src, dst, w = _random_stream(0)
    stats = vertex_stats_from_sample(src[:1000], dst[:1000], w[:1000])
    flat = KMatrix.create(bytes_budget=1 << 16, stats=stats, depth=3, seed=1,
                          partitioner="banded")
    widths = np.asarray(flat.route.widths)
    if np.all((widths & (widths - 1)) == 0):
        pytest.skip("banded plan happened to be all powers of two")
    with pytest.raises(ValueError, match="powers"):
        kma.to_class_layout(flat)


def test_route_offsets_are_the_flat_invariant():
    """Satellite fix: accel route offsets must be the cumsum-slab layout so
    one route table serves both layouts."""
    acc = _accel(seed=4)
    widths = np.asarray(acc.route.widths).astype(np.int64)
    expect = np.concatenate([[0], np.cumsum(widths**2)[:-1]])
    np.testing.assert_array_equal(np.asarray(acc.route.offsets), expect)


# ------------------------------------------------------------------ merge --
def test_accel_merge_additivity():
    acc = _accel(seed=6)
    s1, d1, w1 = _random_stream(21)
    s2, d2, w2 = _random_stream(22)
    a = kma.ingest(acc, EdgeBatch.from_numpy(s1, d1, w1))
    b = kma.ingest(acc, EdgeBatch.from_numpy(s2, d2, w2))
    both = kma.ingest(a, EdgeBatch.from_numpy(s2, d2, w2))
    merged = kma.merge(a, b)
    assert _leaves_equal(merged.pools, both.pools)
    np.testing.assert_array_equal(np.asarray(merged.conn),
                                  np.asarray(both.conn))
    assert int(merged.overflow) == int(a.overflow) + int(b.overflow)


def test_accel_merge_rejects_mismatched_hash_seeds():
    a = _accel(seed=1, sample_seed=0)
    b = _accel(seed=2, sample_seed=0)  # same plan, different hash family
    with pytest.raises(ValueError, match="hash families"):
        kma.merge(a, b)


def test_accel_merge_rejects_mismatched_partition_plans():
    a = _accel(seed=1, sample_seed=0)
    b = _accel(seed=1, sample_seed=33)  # same seed, different sample/plan
    if a.class_widths != b.class_widths or a.class_counts != b.class_counts:
        with pytest.raises(AssertionError):
            kma.merge(a, b)
    else:
        with pytest.raises(ValueError, match="partition plans"):
            kma.merge(a, b)


def test_accel_empty_like_shares_layout_and_zeroes_counters():
    acc = _accel(seed=8)
    src, dst, w = _random_stream(31)
    acc = kma.ingest(acc, EdgeBatch.from_numpy(src, dst, w),
                     capacity=128, block_b=128)
    empty = kma.empty_like(acc)
    assert all(int(np.asarray(p).sum()) == 0 for p in empty.pools)
    assert int(np.asarray(empty.conn).sum()) == 0
    assert int(empty.overflow) == 0
    # merge(empty, x) == x : the snapshot publish identity
    assert _leaves_equal(kma.merge(empty, acc), acc)


# ------------------------------------------------------------- checkpoint --
def test_accel_checkpoint_roundtrip_bit_exact(tmp_path):
    """Class pools AND overflow accounting survive save/restore bit-exactly
    through the generic npz checkpoint store."""
    acc = _accel(seed=9)
    src, dst, w = _random_stream(41)
    acc = kma.ingest(acc, EdgeBatch.from_numpy(src, dst, w),
                     capacity=128, block_b=128)
    assert int(acc.overflow) > 0  # the round-trip must carry a real tally
    store.save(str(tmp_path), 1, acc, extra={"k": "v"})
    template = kma.empty_like(acc)
    restored, meta = store.restore(str(tmp_path), template)
    assert _leaves_equal(restored, acc)
    assert int(restored.overflow) == int(acc.overflow)


# -------------------------------------------------------------- dispatch --
def test_sketch_backend_resolution(monkeypatch):
    monkeypatch.delenv("REPRO_SKETCH_BACKEND", raising=False)
    assert sketch_backend("pallas") == "pallas"
    assert sketch_backend("flat") == "flat"
    assert sketch_backend(None) in ("flat", "pallas")  # platform pick
    monkeypatch.setenv("REPRO_SKETCH_BACKEND", "pallas")
    assert sketch_backend(None) == "pallas"
    with pytest.raises(ValueError, match="sketch backend"):
        sketch_backend("cuda")


def test_registry_serves_accel_backend_exactly(monkeypatch):
    """End-to-end through the production layers: registry builds the accel
    sketch, snapshot buffer ingests/publishes through it, and the engine's
    answers match the direct oracle on the published snapshot."""
    monkeypatch.delenv("REPRO_SKETCH_BACKEND", raising=False)
    from repro.serving import (QueryEngine, SketchRegistry, mix_for_sketch,
                               synth_requests)
    from repro.serving import engine as eng

    reg = SketchRegistry(depth=3, scale=0.02, sketch_backend="pallas")
    tenant = reg.open("cit-HepPh", "kmatrix", 64, seed=0)
    assert isinstance(tenant.snapshot.sketch, KMatrixAccel)
    tenant.step(2)
    snap = tenant.publish()
    assert tenant.buffer.overflow_edges >= 0
    engine = QueryEngine()
    reqs = synth_requests(48, mix_for_sketch("kmatrix"),
                          n_nodes=tenant.stream.spec.n_nodes, seed=5,
                          heavy_universe=512, heavy_threshold=10.0)
    got = [r.value for r in engine.execute(snap, reqs)]
    want = eng.direct_answers(snap, reqs)
    for g, w in zip(got, want):
        if isinstance(g, tuple):
            np.testing.assert_array_equal(g[0], w[0])
            np.testing.assert_array_equal(g[1], w[1])
        else:
            assert g == w


def test_class_wider_than_kernel_limit_refused_at_creation(monkeypatch):
    """A width class the ingest kernel is not built for is refused when the
    sketch is created (and on relayout), never routed to a scatter path."""
    import importlib

    # the package re-exports the kernel function under the module's name
    matrix_ingest = importlib.import_module("repro.kernels.matrix_ingest")
    flat = kma.to_flat_layout(_accel(seed=2))
    widest = max(_accel(seed=2).class_widths)
    monkeypatch.setattr(matrix_ingest, "MAX_WIDTH", widest // 2)
    with pytest.raises(ValueError, match="exceeds the widest class"):
        _accel(seed=2)
    with pytest.raises(ValueError, match="exceeds the widest class"):
        kma.to_class_layout(flat)
