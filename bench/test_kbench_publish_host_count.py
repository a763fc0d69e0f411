"""The ``publish_host_count_share`` reader, checked against a hand-built
span ring: which of the ingest worker's publishes stamped their epoch from
the host's own edge count, and nothing read where the program keeps no
spans."""
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parent / "src")]

from kbench import discover  # noqa: E402

from repro.obs import trace as obs_trace  # noqa: E402

T_OPEN = 1000.0  # s on the perf_counter clock
WINDOW_MS = 10.0


def _ctx():
    return SimpleNamespace(
        trace=None, window=SimpleNamespace(
            t_open=T_OPEN, t_close=T_OPEN + WINDOW_MS / 1e3))


def _plant(log, name, a_ms, b_ms, key, thread="ingest-t"):
    log.record_span(f"kmatrix.{name}",
                    int(round(T_OPEN * 1e9 + a_ms * 1e6)),
                    int(round(T_OPEN * 1e9 + b_ms * 1e6)), key, thread)


@pytest.fixture
def ring():
    log = obs_trace.reset_trace_log()
    yield log
    obs_trace.reset_trace_log()


@pytest.fixture
def reader():
    return discover.metric_reader(HERE, "publish_host_count_share")


def test_publish_host_count_share_counts_publishes_by_their_epoch(
        ring, reader):
    """Publishes that started in the window, matched to a host-count span
    by (thread, epoch): one whose host-count span began after the window
    closed still counts; a sync publish, a publish before the window and a
    host-count span of another thread do not."""
    _plant(ring, "worker.publish", 3.3, 8.0, 1)
    _plant(ring, "snapshot.publish_sync", 3.4, 7.7, 1)
    _plant(ring, "worker.publish", 9.0, 9.9, 2)
    _plant(ring, "snapshot.publish_sync", 9.1, 9.8, 2)
    assert reader.read(_ctx()) == 0.0
    _plant(ring, "worker.publish", -2.0, -1.0, 0)  # before the window
    _plant(ring, "snapshot.publish_host_count", -1.9, -1.8, 0)
    _plant(ring, "worker.publish", 5.0, 5.5, 3)
    _plant(ring, "snapshot.publish_host_count", 5.1, 5.2, 3)
    _plant(ring, "worker.publish", 6.0, 6.5, 4)
    _plant(ring, "snapshot.publish_host_count", 6.1, 6.2, 4,
           thread="ingest-u")
    _plant(ring, "worker.publish", 9.95, 10.4, 5)
    _plant(ring, "snapshot.publish_host_count", 10.05, 10.1, 5)
    assert reader.read(_ctx()) == pytest.approx(100 * 2 / 5)


@pytest.mark.parametrize("ring_kind", ["empty", "no_spans_kept"])
def test_publish_host_count_share_reads_nothing_without_spans(
        ring, reader, ring_kind, monkeypatch):
    """An empty ring, and a program whose trace log keeps no spans at all
    (as before spans existed), give nothing and raise nothing."""
    if ring_kind == "no_spans_kept":
        monkeypatch.setattr(obs_trace, "get_trace_log", lambda: object())
    assert reader.read(_ctx()) is None
