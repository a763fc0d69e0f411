"""Trace reduction: busy union, idle gaps, grouping by name pattern, host
labels, and the per-layer readers built on them."""
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parent / "src")]

from kbench import discover  # noqa: E402
from kbench import trace as tracing  # noqa: E402
from kbench.trace import Event, TraceData  # noqa: E402

MS = 1e6  # ns


def _toy():
    ops = "XLA Ops"
    mods = "XLA Modules"
    dev = "/device:TPU:0"
    device = [
        Event("jit__ingest_counted(123)", 0 * MS, 4 * MS, mods, dev),
        Event("fusion.12", 0 * MS, 1 * MS, ops, dev),
        Event("matrix_ingest", 1 * MS, 4 * MS, ops, dev),
        Event("jit__publish(7)", 6 * MS, 7 * MS, mods, dev),
        Event("fusion.3", 6 * MS, 7 * MS, ops, dev),
        Event("matrix_ingest", 8 * MS, 9.5 * MS, ops, dev),  # overlaps next
        Event("fusion.12", 9 * MS, 10 * MS, ops, dev),
    ]
    host = [Event("bench.window", 0, 12 * MS, "python", "/host:CPU"),
            Event("bench.submit", 3.5 * MS, 7.5 * MS, "python", "/host:CPU"),
            Event("bench.query_wait", 10.5 * MS, 12 * MS, "python",
                  "/host:CPU")]
    return TraceData(device, host, 0.0, 12 * MS)


def test_union_merges_overlaps_and_clips_to_window():
    iv = [(0, 4), (3, 5), (7, 8), (-2, 1), (11, 20)]
    assert tracing.union_ns(iv, 0, 12) == 5 + 1 + 1


def test_idle_gaps_are_the_uncovered_stretches():
    assert tracing.idle_gaps([(1, 2), (2, 3), (5, 6)], 0, 8) == [
        (0, 1), (3, 5), (6, 8)]


def test_busy_time_uses_the_ops_line():
    tr = _toy()
    # ops cover 0-4, 6-7, 8-10 ms
    assert tr.busy_s() == pytest.approx(7e-3)
    assert tr.window_s == pytest.approx(12e-3)


def test_grouping_by_pattern_and_breakdown():
    tr = _toy()
    mods = tr.line(tracing.MODULES_LINE)
    assert [e.name for e in tracing.matching(mods, [r"_ingest_counted"])] \
        == ["jit__ingest_counted(123)"]
    bd = tracing.breakdown(tr)
    kinds = dict(bd["device_ops"])
    assert kinds["matrix_ingest"] == pytest.approx(4.5e-3)
    assert kinds["fusion"] == pytest.approx(3e-3)
    gaps = bd["idle_gaps"]
    # 4-6 ms (submit overlaps 4-6), 10-12 ms (query_wait from 10.5), 7-8 ms
    assert sorted(g[0] for g in gaps[:2]) == ["bench.query_wait",
                                              "bench.submit"]
    assert gaps[0][1] == pytest.approx(2e-3)
    assert gaps[2] == ["bench.submit", pytest.approx(1e-3)]


def test_round_trip_through_json():
    tr = _toy()
    back = TraceData.from_json(tr.to_json())
    assert back.busy_s() == tr.busy_s()
    assert back.host == tr.host


def _reader(name):
    return discover.metric_reader(HERE, name)


def test_device_readers_on_the_toy_trace():
    ctx = SimpleNamespace(trace=_toy())
    idle = _reader("device_idle_share.ingest").read(ctx)
    assert idle == pytest.approx(100 * 5 / 12)
    assert _reader("publish_device_ms").read(ctx) == pytest.approx(1.0)
    assert _reader("closure_device_ms").read(ctx) is None  # nothing to read


def test_readers_return_nothing_without_a_device():
    empty = TraceData([], [], 0.0, 1e9)
    assert _reader("device_idle_share.serve").read(
        SimpleNamespace(trace=empty)) is None


EXCERPTS = sorted((HERE / "testdata").glob("trace_excerpt_*.json"))


@pytest.mark.parametrize("path", EXCERPTS, ids=lambda p: p.stem)
def test_recorded_chip_trace_reduces(path):
    """A 10 ms slice of a v5e trace of an ingest cell: ops found, busy within
    the window, the ingest kernel found by its name and first in time."""
    tr = TraceData.from_json(path.read_text())
    assert tr.line(tracing.OPS_LINE)
    assert 0 < tr.busy_s() <= tr.window_s
    bd = tracing.breakdown(tr)
    assert 0 < len(bd["device_ops"]) <= 10
    assert bd["device_ops"][0][0].startswith("matrix_ingest s32[7,")
    assert tracing.matching(tr.line(tracing.OPS_LINE), [r"matrix_ingest"])
