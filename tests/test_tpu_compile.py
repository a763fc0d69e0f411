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

from repro.kernels import matrix_ingest, matrix_lookup, reach_step
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


@pytest.mark.parametrize("d,p,w", [
    (7, 7, 16), (7, 1, 512), (7, 1, 1024),
    (1, 1, 520),  # row axis padded to whole 264-row blocks
    (1, 1, MAX_WIDTH),  # the widest class KMatrixAccel.create accepts
])
def test_matrix_ingest_compiles(one_chip, d, p, w):
    c = 1024
    fn = jax.jit(lambda pool, hi, hj, wt: matrix_ingest(
        pool, hi, hj, wt, block_b=128, interpret=False))
    compiled = fn.lower(_int32((d, p, w, w), one_chip),
                        _int32((d, p, c), one_chip),
                        _int32((d, p, c), one_chip),
                        _int32((p, c), one_chip)).compile()
    _assert_kernel(compiled)


def test_matrix_lookup_compiles(one_chip):
    d, p, w, c = 7, 3, 256, 1024
    fn = jax.jit(lambda pool, hi, hj: matrix_lookup(
        pool, hi, hj, block_q=256, interpret=False))
    compiled = fn.lower(_int32((d, p, w, w), one_chip),
                        _int32((d, p, c), one_chip),
                        _int32((d, p, c), one_chip)).compile()
    _assert_kernel(compiled)


@pytest.mark.parametrize("w", [512, 1024])
def test_reach_step_compiles(one_chip, w):
    fn = jax.jit(lambda r: reach_step(r, block=128, interpret=False))
    reach = jax.ShapeDtypeStruct((w, w), jnp.float32, sharding=one_chip)
    _assert_kernel(fn.lower(reach).compile())


@pytest.mark.parametrize("budget_kb", [512, 64 * 1024])
def test_kmatrix_accel_ingest_step_compiles(one_chip, monkeypatch, budget_kb):
    """The whole jitted ingest step of the launchers' cit-HepPh config
    (scale 1.0, d=7, 8192-edge batches) at the default and a 64 MiB budget:
    the latter has classes up to 512 wide and a 489-wide conn matrix."""
    from repro.core import KMatrixAccel, vertex_stats_from_sample
    from repro.kernels import ops
    from repro.streams import make_stream, sample_stream

    # this process runs JAX on the CPU; the chip would take the Mosaic path
    monkeypatch.setattr(ops, "interpret_mode", lambda: False)
    stream = make_stream("cit-HepPh", batch_size=8192, seed=0, scale=1.0)
    stats = vertex_stats_from_sample(*sample_stream(stream, 30_000, seed=1))
    sk = KMatrixAccel.create(bytes_budget=budget_kb * 1024, stats=stats,
                             depth=7, seed=0, partitioner="banded")
    if budget_kb == 512:
        assert max(sk.class_counts) > 1  # P_c > 1 classes
    else:
        assert max(sk.class_widths) >= 512 and sk.conn_w == 489
    shapes = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        (sk, stream.batch(0)))
    _assert_kernel(jax.jit(ops.kmatrix_accel_ingest).lower(*shapes).compile())
