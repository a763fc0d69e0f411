"""The ``ingest_overflow_share`` reader on a tiny cell of the width-class
(Pallas, interpreted) layout: 0 where every partition fits its class's
dispatch capacity, the share of dispatched rows that took the scatter
fallback where the capacity is forced small, and nothing where no row was
dispatched."""
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parent / "src")]

from kbench import discover, drive, tiny  # noqa: E402


@pytest.fixture
def reader():
    return discover.metric_reader(HERE, "ingest_overflow_share")


@pytest.fixture
def pallas(monkeypatch):
    import jax

    monkeypatch.setenv("REPRO_SKETCH_BACKEND", "pallas")
    jax.clear_caches()  # no ingest step compiled under another capacity
    yield
    jax.clear_caches()


def _cell(seed):
    cell = drive.Cell(tiny.CONFIG, tiny.INGEST, seed)
    cell.setup()
    return cell


@pytest.mark.parametrize("forced", [False, True], ids=["plan", "forced"])
def test_ingest_overflow_share_reads_the_fallback_rows(
        reader, pallas, monkeypatch, forced):
    from repro.kernels import ops

    if forced:  # one block per partition: the hot ones overflow
        monkeypatch.setattr(ops, "dispatch_capacity",
                            lambda sk, b, block_b=128:
                            (block_b,) * len(sk.class_widths))
    cell = _cell(2 ** 35 + 3)
    try:
        assert cell.layout == "pallas"
        cell.run(0.5)
        ctx = SimpleNamespace(cell=cell)
        got = reader.read(ctx)
        rows = cell.handle.worker.metrics_snapshot()["dedup_unique_rows"]
        over = int(cell.tenant.snapshot.sketch.overflow)
        assert rows > 0
        if forced:
            assert 0 < over < rows
        else:
            assert over == 0
        assert got == pytest.approx(100.0 * over / rows)
    finally:
        cell.release()


def test_ingest_overflow_share_reads_nothing_before_a_dispatch(reader,
                                                                pallas):
    """Set-up warms the dispatch shapes straight through the buffer, not
    through the worker, so no row has been dispatched yet."""
    cell = _cell(7)
    try:
        assert reader.read(SimpleNamespace(cell=cell)) is None
        cell.runtime.stop(drain=True, timeout=60)
    finally:
        cell.release()
