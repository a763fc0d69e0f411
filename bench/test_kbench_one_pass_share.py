"""The ``ingest_one_pass_share`` reader on a tiny cell of the width-class
(Pallas, interpreted) layout: the share of dedup dispatches whose weights
all fit the ingest kernel's one bfloat16 pass, below 100% where 8192-edge
client batches pre-aggregate to weights past 256, and nothing where no
dispatch went through the dedup path or the program counts none."""
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parent / "src")]

from kbench import discover, drive, tiny  # noqa: E402


@pytest.fixture
def reader():
    return discover.metric_reader(HERE, "ingest_one_pass_share")


@pytest.fixture
def pallas(monkeypatch):
    import jax

    monkeypatch.setenv("REPRO_SKETCH_BACKEND", "pallas")
    jax.clear_caches()
    yield
    jax.clear_caches()


def _cell(seed, traffic=tiny.INGEST):
    cell = drive.Cell(tiny.CONFIG, traffic, seed)
    cell.setup()
    return cell


@pytest.mark.parametrize("client_batch", [2048, 8192])
def test_ingest_one_pass_share_reads_the_counted_dispatches(
        reader, pallas, client_batch):
    cell = _cell(2 ** 35 + 5, dict(tiny.INGEST, client_batch=client_batch))
    try:
        assert cell.layout == "pallas"
        cell.run(0.5)
        got = reader.read(SimpleNamespace(cell=cell))
        snap = cell.handle.worker.metrics_snapshot()
        assert snap["dedup_dispatches"] > 0
        assert got == pytest.approx(100.0 * snap["one_pass_dispatches"]
                                    / snap["dedup_dispatches"])
        if client_batch == 8192:  # the batches the bfloat16 control rounds
            assert got < 100.0
    finally:
        cell.release()


def test_ingest_one_pass_share_reads_nothing_before_a_dispatch(reader,
                                                                pallas):
    """Set-up warms the dispatch shapes straight through the buffer, not
    through the worker, so no dispatch has been counted yet."""
    cell = _cell(11)
    try:
        assert reader.read(SimpleNamespace(cell=cell)) is None
        cell.runtime.stop(drain=True, timeout=60)
    finally:
        cell.release()


def test_ingest_one_pass_share_reads_nothing_without_the_counters(reader):
    """A program that does not count one-pass dispatches has nothing to
    read, and the reader does not raise."""
    worker = SimpleNamespace(metrics_snapshot=lambda: {
        "dedup_raw_rows": 10, "dedup_unique_rows": 5})
    ctx = SimpleNamespace(cell=SimpleNamespace(
        handle=SimpleNamespace(worker=worker)))
    assert reader.read(ctx) is None
