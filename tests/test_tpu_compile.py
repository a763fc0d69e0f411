"""Compile the main path's Pallas kernels for a described TPU v5e chip.

Nothing runs: the chip's compiler lowers each kernel (and the whole
width-class ingest step) at deployment widths and refuses what would not
fit — unaligned block shapes, too much VMEM.  The topology is described
inside a fixture, never at import, so every xdist worker collects the same
tests and only the worker running this file loads the TPU compiler.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import matrix_ingest, reach_step
from repro.kernels.matrix_ingest import MAX_WIDTH


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A chip compile written to the persistent cache cannot be read back
    without the chip; keep the cache off while these tests run."""
    from jax.experimental.compilation_cache import compilation_cache

    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _int32(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


def _assert_both_passes(compiled, n_classes):
    """Each class's ingest holds both kernels, the one bfloat16 pass and
    the fp32 contraction, as the branches of a conditional."""
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 2 * n_classes
    assert text.count(" conditional(") == n_classes


@pytest.mark.parametrize("d,p,w,c", [
    pytest.param(7, 7, 16, 1024, id="7-7-16"),
    pytest.param(7, 1, 512, 1024, id="7-1-512"),
    pytest.param(7, 1, 1024, 1024, id="7-1-1024"),
    # row axis padded to whole 264-row blocks
    pytest.param(1, 1, 520, 1024, id="1-1-520"),
    # the widest class KMatrixAccel.create accepts
    pytest.param(1, 1, MAX_WIDTH, 1024, id=f"1-1-{MAX_WIDTH}"),
    # sx-stackoverflow at 150 MiB: its three classes at a whole 8192-row
    # dispatch, the capacity its plan's hottest partition sets
    (7, 15, 256, 8192), (7, 1, 512, 8192), (7, 1, 1024, 8192),
])
def test_matrix_ingest_compiles(one_chip, d, p, w, c):
    fn = jax.jit(lambda pool, hi, hj, wt: matrix_ingest(
        pool, hi, hj, wt, block_b=128, interpret=False))
    compiled = fn.lower(_int32((d, p, w, w), one_chip),
                        _int32((d, p, c), one_chip),
                        _int32((d, p, c), one_chip),
                        _int32((p, c), one_chip)).compile()
    _assert_kernel(compiled)
    if c == 8192:
        _assert_both_passes(compiled, 1)


@pytest.mark.parametrize("w", [512, 1024])
def test_reach_step_compiles(one_chip, w):
    fn = jax.jit(lambda r: reach_step(r, block=128, interpret=False))
    reach = jax.ShapeDtypeStruct((w, w), jnp.float32, sharding=one_chip)
    _assert_kernel(fn.lower(reach).compile())


@pytest.mark.parametrize("dataset,budget_kb", [
    pytest.param("cit-HepPh", 512, id="512"),
    pytest.param("cit-HepPh", 64 * 1024, id="65536"),
    pytest.param("sx-stackoverflow", 153_600, id="sx-stackoverflow-153600"),
])
def test_kmatrix_accel_ingest_step_compiles(one_chip, monkeypatch, dataset,
                                            budget_kb):
    """The whole jitted ingest step (scale 1.0, d=7, 8192-edge batches) of
    the launchers' cit-HepPh config at the default and a 64 MiB budget, and
    of the 150 MiB sx-stackoverflow deployment.  64 MiB has classes up to
    512 wide and a 489-wide conn matrix; 150 MiB has fifteen 256-wide
    partitions, a 512 and a 1024 class and a 749-wide conn matrix, each
    class at its own dispatch capacity (only the 1024 class, which carries
    most of the stream, at the whole batch)."""
    import dataclasses

    from repro.core import KMatrixAccel, vertex_stats_from_sample
    from repro.core.kmatrix_accel import dispatch_capacity
    from repro.kernels import ops
    from repro.streams import DATASETS, SyntheticStream, sample_stream

    # this process runs JAX on the CPU; the chip would take the Mosaic path
    monkeypatch.setattr(ops, "interpret_mode", lambda: False)
    spec = DATASETS[dataset]
    if dataset == "sx-stackoverflow":
        # the plan comes from the 30k-edge sample alone; sampling a 2M-edge
        # prefix of the stream gives the same classes in a second
        spec = dataclasses.replace(spec, n_edges=2_000_000)
    stream = SyntheticStream(spec, batch_size=8192, seed=0)
    stats = vertex_stats_from_sample(*sample_stream(stream, 30_000, seed=1))
    sk = KMatrixAccel.create(bytes_budget=budget_kb * 1024, stats=stats,
                             depth=7, seed=0, partitioner="banded")
    if budget_kb == 512:
        assert max(sk.class_counts) > 1  # P_c > 1 classes
    elif dataset == "cit-HepPh":
        assert max(sk.class_widths) >= 512 and sk.conn_w == 489
    else:
        assert dict(zip(sk.class_widths, sk.class_counts)) == {
            256: 15, 512: 1, 1024: 1}
        assert sk.conn_w == 749
        assert dispatch_capacity(sk, 8192) == (640, 5120, 8192)
    shapes = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        (sk, stream.batch(0)))
    compiled = jax.jit(ops.kmatrix_accel_ingest).lower(*shapes).compile()
    _assert_kernel(compiled)
    if dataset == "sx-stackoverflow":
        _assert_both_passes(compiled, 3)
