"""The readers of the program's own spans (``kbench.spans`` and the
``worker_*``, ``publish_sync_ms`` and ``idle_worker_host_share`` metrics),
checked against a hand-built span ring and the device events of a recorded
v5e trace; and the device names the trace readers key on, pinned to what
the program's jitted steps lower to."""
import re
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parent / "src")]

from kbench import discover  # noqa: E402
from kbench import spans as kspans  # noqa: E402
from kbench.trace import TraceData  # noqa: E402

from repro.obs import trace as obs_trace  # noqa: E402

T_OPEN = 1000.0  # s on the perf_counter clock
WINDOW_MS = 10.0
EMAIL = HERE / "testdata" / "trace_excerpt_email_ingest.json"
# the excerpt's device is idle from its first instant for this long, and
# busy at 2.8268315 ms
GAP_MS = 2.8268315


def _ns(ms: float) -> int:
    return int(round(T_OPEN * 1e9 + ms * 1e6))


def _ctx(trace=None):
    return SimpleNamespace(
        trace=trace, window=SimpleNamespace(
            t_open=T_OPEN, t_close=T_OPEN + WINDOW_MS / 1e3))


def _plant(log, name, a_ms, b_ms, key, thread="ingest-t"):
    log.record_span(f"kmatrix.{name}", _ns(a_ms), _ns(b_ms), key, thread)


def _reader(name):
    return discover.metric_reader(HERE, name)


@pytest.fixture
def ring():
    log = obs_trace.reset_trace_log()
    yield log
    obs_trace.reset_trace_log()


def _email():
    return TraceData.from_json(EMAIL.read_text())


def _plant_work(log):
    """Two dispatches on one worker, a third unit on another thread, two
    publishes, and spans outside the window on both sides."""
    _plant(log, "worker.dedup", -5.0, -4.0, -1)  # before the window
    _plant(log, "worker.dedup", 0.0, 1.0, 0)
    _plant(log, "worker.stage", 1.0, 1.5, 0)
    _plant(log, "worker.dispatch", 1.5, 3.0, 0)
    _plant(log, "worker.reservoir", 3.0, 3.25, 0)
    _plant(log, "worker.dedup", 4.0, 7.0, 1)
    _plant(log, "worker.stage", 7.0, 8.0, 1)
    _plant(log, "worker.dispatch", 8.0, 9.0, 1)
    _plant(log, "worker.reservoir", 9.0, 9.75, 1)
    _plant(log, "worker.stage", 2.0, 3.0, 0, thread="ingest-u")
    _plant(log, "worker.publish", 3.3, 8.0, 1)
    _plant(log, "snapshot.publish_sync", 3.4, 7.7, 1)
    _plant(log, "worker.publish", 9.0, 9.9, 2)
    _plant(log, "snapshot.publish_sync", 9.1, 9.8, 2)
    _plant(log, "snapshot.publish_sync", 10.0, 12.0, 3)  # after the window


@pytest.mark.parametrize("metric,want", [
    ("worker_dedup_ms", (1.0 + 3.0) / 2),
    ("worker_reservoir_ms", (0.25 + 0.75) / 2),
    ("worker_dispatch_ms", ((0.5 + 1.5) + (1.0 + 1.0) + 1.0) / 3),
    ("publish_sync_ms", (4.3 + 0.7) / 2),
])
def test_span_readers_average_per_unit_of_work(ring, metric, want):
    _plant_work(ring)
    assert _reader(metric).read(_ctx()) == pytest.approx(want)


SPAN_METRICS = ["worker_dedup_ms", "worker_reservoir_ms",
                "worker_dispatch_ms", "publish_sync_ms",
                "idle_worker_host_share"]


@pytest.mark.parametrize("metric", SPAN_METRICS)
def test_span_readers_read_nothing_without_spans(ring, metric, monkeypatch):
    """An empty ring, and a program whose trace log keeps no spans at all
    (as before spans existed), give nothing and raise nothing."""
    ctx = _ctx(_email())
    assert _reader(metric).read(ctx) is None
    monkeypatch.setattr(obs_trace, "get_trace_log", lambda: object())
    assert _reader(metric).read(ctx) is None


def test_span_readers_refuse_a_ring_that_dropped_window_spans(monkeypatch):
    monkeypatch.setattr(obs_trace, "SPAN_CAPACITY", 4)
    try:
        log = obs_trace.reset_trace_log()
        _plant(log, "worker.dedup", -3.0, -2.5, -2)
        _plant(log, "worker.dedup", -2.0, -1.5, -1)
        for k in range(3):
            _plant(log, "worker.dedup", k, k + 0.5, k)
        assert log.spans_dropped == 1  # dropped before the window opened
        assert _reader("worker_dedup_ms").read(_ctx()) == pytest.approx(0.5)
        _plant(log, "worker.dedup", 3.0, 3.5, 3)
        _plant(log, "worker.dedup", 4.0, 4.5, 4)
        assert log.spans_dropped == 3  # and now one of the window's
        assert _reader("worker_dedup_ms").read(_ctx()) is None
    finally:
        obs_trace.reset_trace_log()


def test_idle_worker_host_share_counts_a_planted_gap(ring):
    tr = _email()
    idle_s = tr.window_s - tr.busy_s()
    _plant(ring, "worker.stage", -1.0, 0.4, 0)  # began before the window
    _plant(ring, "worker.dedup", 0.5, 2.0, 1)
    _plant(ring, "worker.publish", 2.2, 2.8, 1)
    _plant(ring, "snapshot.publish_sync", 2.3, 2.6, 1)
    _plant(ring, "worker.queue_get", 3.0, 9.0, 2)  # not host work
    ctx = _ctx(tr)
    split = kspans.idle_split(ctx)
    assert sum(split.values()) == pytest.approx(idle_s, abs=1e-12)
    assert "kmatrix.worker.stage" not in split
    assert split["kmatrix.worker.dedup"] == pytest.approx(1.5e-3)
    assert split["kmatrix.worker.publish"] == pytest.approx(0.3e-3)
    assert split["kmatrix.snapshot.publish_sync"] == pytest.approx(0.3e-3)
    assert 0 < split["kmatrix.worker.queue_get"] < idle_s - GAP_MS / 1e3
    share = _reader("idle_worker_host_share").read(ctx)
    assert share == pytest.approx(100 * (1.5 + 0.3) / WINDOW_MS)
    label, longest, at = kspans.longest_gaps(ctx)[0]
    assert longest == pytest.approx(GAP_MS / 1e3) and at == 0.0
    parts = label.split(" + ")
    assert parts[:2] == ["kmatrix.worker.dedup 1.500 ms", "no span 0.727 ms"]
    assert set(parts[2:]) == {"kmatrix.worker.publish 0.300 ms",
                              "kmatrix.snapshot.publish_sync 0.300 ms"}
    assert _reader("idle_worker_host_share").read(_ctx(None)) is None


def test_innermost_names_each_piece_by_the_span_that_started_last():
    spans = [(0, 10, "publish"), (2, 5, "publish_sync"), (4, 12, "other"),
             (20, 30, "dedup"), (20, 25, "inner")]
    assert kspans.innermost(spans) == [
        (0, 2, "publish"), (2, 4, "publish_sync"), (4, 12, "other"),
        (20, 25, "inner"), (25, 30, "dedup")]
    assert kspans.overlap_by_name([(1, 3), (11, 22)],
                                  kspans.innermost(spans)) == {
        "publish": 1, "publish_sync": 1, "other": 1, "inner": 2}


def test_worker_coverage_is_the_union_of_the_worker_spans(ring):
    _plant(ring, "worker.queue_get", 0.0, 6.0, 0)
    _plant(ring, "worker.dedup", 6.0, 8.0, 0)
    _plant(ring, "snapshot.publish_sync", 8.0, 10.0, 1)  # not the worker's
    assert kspans.worker_coverage(_ctx()) == pytest.approx(0.8)


# ---------------------------------------------------- device names pinned


def _module_name(lowered) -> str:
    """The name of the jitted program, as a trace's ``XLA Modules`` line
    shows it (there with an ``(<id>)`` suffix)."""
    return re.match(r"module @(\S+)", lowered.as_text()).group(1)


@pytest.fixture(scope="module")
def programs():
    import jax.numpy as jnp

    from repro.core import queries
    from repro.core.types import EdgeBatch
    from repro.serving import SketchRegistry

    reg = SketchRegistry(depth=3, batch_size=1024, scale=0.02)
    buf = reg.open("cit-HepPh", "kmatrix", 64, seed=0).buffer
    kit = buf._kernels
    z = np.zeros(256, np.int32)
    adj = jnp.zeros((2, 16, 16), jnp.int32)
    return {
        "ingest_counted": _module_name(kit.ingest_counted.lower(
            buf._delta, EdgeBatch.from_numpy(z, z, z), 0, buf._pending)),
        "publish": _module_name(kit.publish.lower(buf.snapshot.sketch,
                                                  buf._delta)),
        "closure_jnp": _module_name(
            queries._build_closure_jnp.lower(adj, None)),
        "closure_pallas": _module_name(
            queries._build_closure_pallas.lower(adj, 4, 8)),
    }


READS = {"ingest_step_roofline": ["ingest_counted"],
         "publish_device_ms": ["publish"],
         "closure_device_ms": ["closure_jnp", "closure_pallas"]}


@pytest.mark.parametrize("metric", sorted(READS))
def test_trace_readers_match_the_programs_they_read(programs, metric):
    """Each device-trace reader's name patterns match the program it
    reads, as the trace names it, and no other of these programs: a rename
    fails here instead of silently nulling the metric."""
    patterns = [re.compile(p) for p in _reader(metric).PATTERNS]
    for prog, name in programs.items():
        hit = any(p.search(f"{name}(12)") for p in patterns)
        assert hit == (prog in READS[metric]), (metric, prog, name)
