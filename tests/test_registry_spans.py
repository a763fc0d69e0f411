"""Set-up spans of ``SketchRegistry.open``: the bootstrap sample, the
partition plan and the snapshot buffers each record one span keyed by the
tenant id, once per tenant; and the benchmark's ``registry_sample_s``
reader, which reads the sample span back from the ring."""
import importlib.util
from pathlib import Path
from types import SimpleNamespace

import pytest

from repro.obs import trace as obs_trace
from repro.serving.registry import SketchRegistry

READER = (Path(__file__).resolve().parents[1] / "bench" / "metrics"
          / "registry_sample_s.py")
PHASES = ["kmatrix.registry.sample", "kmatrix.registry.plan",
          "kmatrix.registry.alloc"]


@pytest.fixture
def ring():
    log = obs_trace.reset_trace_log()
    yield log
    obs_trace.reset_trace_log()


def _registry():
    return SketchRegistry(depth=3, batch_size=1024, sample_size=30_000,
                          scale=0.02, sketch_backend="flat")


def _registry_spans(log):
    return [s for s in log.spans() if s.name.startswith("kmatrix.registry.")]


def _reader():
    spec = importlib.util.spec_from_file_location("registry_sample_s", READER)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_open_records_sample_plan_alloc_in_order_once(ring):
    reg = _registry()
    tenant = reg.open("cit-HepPh", "kmatrix", 64, seed=0)
    spans = _registry_spans(ring)
    assert [s.name for s in spans] == PHASES
    assert {s.key for s in spans} == {tenant.key.tenant_id}
    assert all(a.t1_ns <= b.t0_ns for a, b in zip(spans, spans[1:]))
    assert reg.open("cit-HepPh", "kmatrix", 64, seed=0) is tenant
    assert len(_registry_spans(ring)) == 3  # the second open builds nothing
    other = reg.open("cit-HepPh", "kmatrix", 64, seed=1)
    assert [s.key for s in _registry_spans(ring)[3:]] == \
        [other.key.tenant_id] * 3


def test_open_sharded_records_the_same_phases(ring):
    reg = _registry()
    sharded = reg.open_sharded("cit-HepPh", "kmatrix", 64, seed=0,
                               n_shards=2)
    spans = _registry_spans(ring)
    assert [s.name for s in spans] == PHASES
    assert {s.key for s in spans} == {sharded.key.tenant_id}
    reg.open_sharded("cit-HepPh", "kmatrix", 64, seed=0, n_shards=2)
    assert len(_registry_spans(ring)) == 3


def test_sample_reader_reads_the_tenants_span(ring):
    reg = _registry()
    tenant = reg.open("cit-HepPh", "kmatrix", 64, seed=0)
    reg.open("cit-HepPh", "kmatrix", 64, seed=1)
    ctx = SimpleNamespace(cell=SimpleNamespace(tenant=tenant))
    span = next(s for s in ring.spans() if s.name == PHASES[0]
                and s.key == tenant.key.tenant_id)
    assert _reader().read(ctx) == (span.t1_ns - span.t0_ns) / 1e9


def test_sample_reader_on_a_hand_built_ring(ring, monkeypatch):
    reader = _reader()
    key = SimpleNamespace(tenant_id="sx-stackoverflow/kmatrix/153600kb/s0")
    ctx = SimpleNamespace(cell=SimpleNamespace(
        tenant=SimpleNamespace(key=key)))
    assert reader.read(ctx) is None  # no span: a program without it
    ring.record_span("kmatrix.registry.sample", 10**9, 10**9 + 21_500_000_000,
                     key.tenant_id)
    ring.record_span("kmatrix.registry.sample", 0, 5, "another/tenant")
    assert reader.read(ctx) == pytest.approx(21.5)
    assert reader.read(SimpleNamespace(cell=SimpleNamespace())) is None
    # a ring that has dropped the span reads nothing
    monkeypatch.setattr(obs_trace, "SPAN_CAPACITY", 4)
    small = obs_trace.reset_trace_log()
    small.record_span("kmatrix.registry.sample", 0, 10**9, key.tenant_id)
    for i in range(4):
        small.record_span("kmatrix.worker.dedup", 2 * i, 2 * i + 1, i)
    assert small.spans_dropped == 1
    assert reader.read(ctx) is None
