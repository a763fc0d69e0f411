"""Per-kernel validation: interpret-mode Pallas vs pure-jnp oracle,
swept over shapes/dtypes, plus end-to-end equivalence with the core sketches."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:  # images without hypothesis: skip, don't die
    from _hypothesis_stub import given, settings, st

from repro.core import EdgeBatch, vertex_stats_from_sample
from repro.kernels import matrix_ingest, reach_step, embedding_bag
from repro.kernels import ref
from repro.kernels.ops import (
    KMatrixAccel,
    accel_reach_closure,
    kmatrix_accel_edge_freq,
    kmatrix_accel_ingest,
)


# ---------------------------------------------------------------- ingest --
@pytest.mark.parametrize("d,p,w,c,tb", [
    (1, 1, 8, 32, 32),
    (3, 1, 64, 128, 64),
    (2, 4, 16, 64, 32),
    (7, 2, 128, 256, 128),
    (1, 2, 1024, 128, 128),  # out tile row-blocked (4 blocks of 256 rows)
    (1, 1, 520, 128, 128),  # 2 blocks of 264 rows: row axis padded to 528
    (1, 2, 600, 128, 128),  # 2 blocks of 304 rows: row axis padded to 608
])
def test_matrix_ingest_matches_ref(d, p, w, c, tb):
    rng = np.random.default_rng(d * 100 + w)
    pool = jnp.asarray(rng.integers(0, 50, (d, p, w, w)), jnp.int32)
    hi = jnp.asarray(rng.integers(0, w, (d, p, c)), jnp.int32)
    hj = jnp.asarray(rng.integers(0, w, (d, p, c)), jnp.int32)
    wt = jnp.asarray(rng.integers(0, 4, (p, c)), jnp.int32)
    out = matrix_ingest(pool, hi, hj, wt, block_b=tb, interpret=True)
    expect = ref.matrix_ingest_ref(pool, hi, hj, wt)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(expect))


@given(seed=st.integers(0, 10_000))
@settings(max_examples=15, deadline=None)
def test_matrix_ingest_property(seed):
    rng = np.random.default_rng(seed)
    d, p, w, c = 2, 2, 16, 64
    pool = jnp.zeros((d, p, w, w), jnp.int32)
    hi = jnp.asarray(rng.integers(0, w, (d, p, c)), jnp.int32)
    hj = jnp.asarray(rng.integers(0, w, (d, p, c)), jnp.int32)
    wt = jnp.asarray(rng.integers(0, 3, (p, c)), jnp.int32)
    out = matrix_ingest(pool, hi, hj, wt, block_b=32, interpret=True)
    # mass conservation per (layer, partition)
    np.testing.assert_array_equal(
        np.asarray(out).sum(axis=(2, 3)),
        np.broadcast_to(np.asarray(wt).sum(axis=1), (d, p)),
    )
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(ref.matrix_ingest_ref(pool, hi, hj, wt))
    )


def _poisoned_kernel(hi_ref, hj_ref, wt_ref, pool_ref, out_ref):
    """A kernel body whose result matches no reference: the branch that
    must not run is swapped for it."""
    out_ref[...] = jnp.full(out_ref.shape, -1, out_ref.dtype)


def _one_pass_case(name, rng, d, p, w, c):
    """Weights (P, C) of one case of the one-pass branch's edge cases."""
    wt = rng.integers(-256, 257, (p, c))
    if name == "bounds":
        wt[0, :2] = 256, -256
    elif name == "over":
        wt[0, 0] = 257
    elif name == "under":
        wt[-1, -1] = -257
    return wt


@pytest.mark.parametrize("case,d,p,w,c,one_pass", [
    ("bounds", 2, 2, 16, 256, True),
    ("over", 2, 2, 16, 256, False),
    ("under", 2, 2, 16, 256, False),
    ("tile-sum", 1, 1, 16, 256, True),
    ("sx-256", 2, 3, 256, 128, True),
    ("sx-512", 2, 1, 512, 128, True),
    ("sx-1024", 1, 1, 1024, 128, True),  # row-blocked: 4 blocks of 256
], ids=lambda v: v if isinstance(v, str) else None)
def test_matrix_ingest_one_pass_branch(monkeypatch, case, d, p, w, c,
                                       one_pass):
    """Weights within [-256, 256] take the one bfloat16 pass and weights
    past it the fp32 contraction, both bit-exact against the reference;
    the branch that must not run is poisoned, so the match also shows which
    branch ran.  ``tile-sum`` puts 128 edges of weight 256 on one cell of
    one 128-edge tile: an increment of 2^15."""
    import importlib

    mi = importlib.import_module("repro.kernels.matrix_ingest")
    rng = np.random.default_rng(w + c)
    pool = jnp.asarray(rng.integers(-1000, 1000, (d, p, w, w)), jnp.int32)
    hi = rng.integers(0, w, (d, p, c))
    hj = rng.integers(0, w, (d, p, c))
    if case == "tile-sum":
        wt = np.zeros((p, c), np.int64)
        wt[:, :128] = 256
        hi[:, :, :128], hj[:, :, :128] = w - 1, 3
    else:
        wt = _one_pass_case(case, rng, d, p, w, c)
    hi, hj, wt = (jnp.asarray(a, jnp.int32) for a in (hi, hj, wt))
    assert bool(mi.fits_one_pass(wt)) == one_pass
    idle = "_ingest_kernel" if one_pass else "_ingest_kernel_one_pass"
    jax.clear_caches()
    monkeypatch.setattr(mi, idle, _poisoned_kernel)
    try:
        out = matrix_ingest(pool, hi, hj, wt, block_b=128, interpret=True)
    finally:
        jax.clear_caches()
    expect = ref.matrix_ingest_ref(pool, hi, hj, wt)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(expect))
    if case == "tile-sum":
        assert int(out[0, 0, w - 1, 3] - pool[0, 0, w - 1, 3]) == 2 ** 15


# --------------------------------------------------------------- closure --
@pytest.mark.parametrize("w,block", [(128, 128), (256, 128), (512, 256)])
def test_reach_step_matches_ref(w, block):
    rng = np.random.default_rng(w)
    reach = jnp.asarray((rng.random((w, w)) < 0.02), jnp.float32)
    out = reach_step(reach, block=block, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref.reach_step_ref(reach)), rtol=1e-6
    )


def test_accel_closure_matches_queries_closure():
    rng = np.random.default_rng(1)
    table = jnp.asarray(rng.integers(0, 2, (2, 100, 100)), jnp.int32)
    closed = accel_reach_closure(table, block=128)
    expect = ref.reach_closure_ref(
        (table[0] > 0).astype(jnp.float32), n_steps=7
    )
    np.testing.assert_array_equal(np.asarray(closed[0]), np.asarray(expect) > 0.5)


# ------------------------------------------- closure backend dispatch ----
@pytest.mark.parametrize("max_hops", [None, 1, 2, 7])
def test_build_closure_pallas_backend_parity(max_hops):
    """queries.build_closure must answer identically through the Pallas
    kernel (interpret mode off-TPU) and the pure-jnp cascade — the dispatch
    that closes the ROADMAP `kernels/reach_closure.py` item."""
    from repro.core import queries

    rng = np.random.default_rng(7)
    # deliberately non-power-of-two width: exercises the kernel's padding
    table = jnp.asarray(rng.integers(0, 3, (3, 37, 37)) *
                        (rng.random((3, 37, 37)) < 0.05), jnp.int32)
    jnp_closure = queries.build_closure(table, max_hops, backend="jnp")
    pallas_closure = queries.build_closure(table, max_hops, backend="pallas")
    assert pallas_closure.shape == jnp_closure.shape
    assert pallas_closure.dtype == jnp_closure.dtype == jnp.bool_
    np.testing.assert_array_equal(np.asarray(pallas_closure),
                                  np.asarray(jnp_closure))


def test_build_closure_backend_resolution(monkeypatch):
    from repro.core import queries

    monkeypatch.delenv("REPRO_CLOSURE_BACKEND", raising=False)
    assert queries.closure_backend("pallas") == "pallas"
    assert queries.closure_backend(None) in ("jnp", "pallas")  # platform pick
    monkeypatch.setenv("REPRO_CLOSURE_BACKEND", "pallas")
    assert queries.closure_backend(None) == "pallas"
    with pytest.raises(ValueError, match="closure backend"):
        queries.closure_backend("cuda")


def test_reachability_end_to_end_on_pallas_backend():
    """Full query path (closure_layers -> build_closure -> pair lookup) on
    the Pallas backend agrees with the jnp backend for a real sketch."""
    from repro.core import EdgeBatch, MatrixSketch, queries
    from repro.core import matrix_sketch

    rng = np.random.default_rng(3)
    src = rng.integers(0, 50, 120).astype(np.int32)
    dst = rng.integers(0, 50, 120).astype(np.int32)
    sk = MatrixSketch.create(bytes_budget=1 << 14, depth=3, seed=2)
    sk = matrix_sketch.ingest(sk, EdgeBatch.from_numpy(src, dst))
    qs = jnp.asarray(src[:32], jnp.int32)
    qd = jnp.asarray(dst[::-1][:32], jnp.int32)
    hi, hj = queries.reach_cells(sk, qs), queries.reach_cells(sk, qd)
    layers = queries.closure_layers(sk)
    for max_hops in (None, 2):
        a = queries.reachability_from_closure(
            queries.build_closure(layers, max_hops, backend="jnp"), hi, hj)
        b = queries.reachability_from_closure(
            queries.build_closure(layers, max_hops, backend="pallas"), hi, hj)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ----------------------------------------------------------- embedding ----
@pytest.mark.parametrize("v,d_,b,f", [(64, 128, 8, 4), (1000, 128, 16, 39), (32, 256, 4, 2)])
def test_embedding_bag_matches_ref(v, d_, b, f):
    rng = np.random.default_rng(v + b)
    table = jnp.asarray(rng.normal(size=(v, d_)), jnp.float32)
    idx = jnp.asarray(rng.integers(0, v, (b, f)), jnp.int32)
    out = embedding_bag(table, idx, interpret=True)
    # Sequential in-kernel accumulation vs XLA tree-reduce: order differs,
    # so allow a few ULPs on the long (F=39) reductions.
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref.embedding_bag_ref(table, idx)),
        rtol=1e-5, atol=1e-5,
    )


def test_embedding_bag_weighted():
    rng = np.random.default_rng(0)
    table = jnp.asarray(rng.normal(size=(50, 128)), jnp.float32)
    idx = jnp.asarray(rng.integers(0, 50, (6, 5)), jnp.int32)
    wts = jnp.asarray(rng.normal(size=(6, 5)), jnp.float32)
    out = embedding_bag(table, idx, wts, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref.embedding_bag_ref(table, idx, wts)),
        rtol=1e-5, atol=1e-5,
    )


# ------------------------------------------------- end-to-end ops layer ---
def test_kmatrix_accel_exact_counting():
    """Class-layout ingest (dispatch + kernel + overflow) never loses edges."""
    rng = np.random.default_rng(11)
    src = rng.zipf(1.3, 4096).astype(np.int32) % 2000
    dst = rng.integers(0, 2000, 4096).astype(np.int32)
    stats = vertex_stats_from_sample(src[:1000], dst[:1000])
    sk = KMatrixAccel.create(bytes_budget=1 << 16, stats=stats, depth=3, seed=1)
    batch = EdgeBatch.from_numpy(src, dst)
    # tiny capacity forces the overflow path
    out = kmatrix_accel_ingest(sk, batch, capacity=128, block_b=128)
    total = sum(np.asarray(p).sum(axis=(1, 2, 3)) for p in out.pools)
    np.testing.assert_array_equal(total, np.full(3, 4096))  # per-layer mass
    est = np.asarray(kmatrix_accel_edge_freq(out, jnp.asarray(src), jnp.asarray(dst)))
    from repro.core.metrics import exact_edge_frequencies, lookup_exact
    true = lookup_exact(exact_edge_frequencies(src, dst), src, dst)
    assert (est >= true - 1e-6).all()


def test_kmatrix_accel_capacity_invariance():
    """Estimates identical whichever path (kernel vs overflow) edges took."""
    rng = np.random.default_rng(12)
    src = rng.integers(0, 300, 1024).astype(np.int32)
    dst = rng.integers(0, 300, 1024).astype(np.int32)
    stats = vertex_stats_from_sample(src[:400], dst[:400])
    sk = KMatrixAccel.create(bytes_budget=1 << 15, stats=stats, depth=2, seed=3)
    batch = EdgeBatch.from_numpy(src, dst)
    small = kmatrix_accel_ingest(sk, batch, capacity=128, block_b=128)
    large = kmatrix_accel_ingest(sk, batch, capacity=1024, block_b=128)
    for a, b in zip(small.pools, large.pools):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
