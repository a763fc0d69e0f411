"""Unified telemetry tier (ISSUE 7): mergeable metrics hub, cross-transport
trace spans, and the scrapeable exposition surface (DESIGN.md §Observability).

The load-bearing gates: log-bucketed histograms merge associatively /
commutatively and EXACTLY match a one-shot histogram over the raw samples
(so per-worker distributions sum across threads, pipes and socket frames);
the Prometheus text a server scrapes renders histogram sums equal to the
per-worker histograms merged parent-side; one edge batch's trace chain
closes enqueue -> dispatch -> publish -> adopt across a real socket worker;
and the ``metrics`` frame sits behind the same auth gate as query frames.
"""
import json
import os
import socket
import threading
import time
import types

import numpy as np
import pytest

from repro.obs import (
    Histogram,
    LADDERS,
    MetricsHub,
    MetricsJsonDumper,
    get_hub,
    get_trace_log,
    hist_summary,
    merge_hist_states,
    quantile_from_state,
    render_prometheus,
    reset_hub,
    reset_trace_log,
)
from repro.obs.dashboard import parse_prometheus_text
from repro.runtime.metrics import RateEWMA, WorkerMetrics


@pytest.fixture(autouse=True)
def _fresh_telemetry():
    reset_hub()
    reset_trace_log()
    yield
    reset_hub()
    reset_trace_log()


def _registry(**kw):
    from repro.serving import SketchRegistry

    kw.setdefault("depth", 3)
    kw.setdefault("batch_size", 1024)
    kw.setdefault("scale", 0.02)
    return SketchRegistry(**kw)


# ------------------------------------------------------------ histograms


def test_histogram_merge_matches_raw_oracle(rng):
    """Per-chunk histograms merged in ANY order/grouping must equal the
    one-shot histogram over all raw samples — counts, sum, min, max."""
    xs = rng.exponential(0.004, 3000)
    chunks = np.array_split(xs, 3)
    hs = []
    for i, chunk in enumerate(chunks):
        h = Histogram(f"h{i}", {})
        h.observe_many(chunk)
        hs.append(h.state())
    oracle = Histogram("all", {})
    oracle.observe_many(xs)
    want = oracle.state()

    left = merge_hist_states(merge_hist_states(hs[0], hs[1]), hs[2])
    right = merge_hist_states(hs[0], merge_hist_states(hs[1], hs[2]))
    flipped = merge_hist_states(hs[2], merge_hist_states(hs[1], hs[0]))
    for merged in (left, right, flipped):
        assert merged["counts"] == want["counts"]
        assert merged["count"] == want["count"] == len(xs)
        assert merged["sum"] == pytest.approx(want["sum"], abs=1e-9)
        assert merged["min"] == want["min"]
        assert merged["max"] == want["max"]
    # associativity/commutativity exactly (integer counts, float adds of
    # the same operands in the same association are compared approx)
    assert left["counts"] == right["counts"] == flipped["counts"]

    # bucket-interpolated quantiles track the raw-sample oracle within a
    # bucket width (the ladder grows by sqrt(2), so <= ~42% relative) and
    # clamp to the observed extremes
    for q in (0.5, 0.9, 0.99):
        est = quantile_from_state(left, q)
        raw = float(np.quantile(xs, q))
        assert raw / 1.5 <= est <= raw * 1.5
        assert want["min"] <= est <= want["max"]


def test_histogram_ladders_and_summary():
    assert len(LADDERS["latency"]) == 54
    assert len(LADDERS["size"]) == 25
    with pytest.raises(ValueError):
        Histogram("bad", {}, ladder="nope")
    a = Histogram("a", {}, ladder="size")
    b = Histogram("b", {})
    with pytest.raises(ValueError, match="ladder"):
        merge_hist_states(a.state(), b.state())

    h = Histogram("s", {})
    h.observe_n(0.25, 7)  # weighted single-bucket update
    s = hist_summary(h.state())
    assert s["count"] == 7
    assert s["mean"] == pytest.approx(0.25)
    assert hist_summary(Histogram("empty", {}).state()) == {"count": 0}


def test_hub_adopt_merges_exactly_and_renders_parseable(rng):
    """Acceptance gate: the scraped exposition's histogram sums equal the
    per-worker histograms merged parent-side — exactly."""
    child_samples = {"w1": rng.exponential(0.01, 400),
                     "w2": rng.exponential(0.002, 700)}
    parent = MetricsHub()
    for name, xs in child_samples.items():
        child = MetricsHub()  # stands in for a remote worker's hub
        child.counter("repro_ingest_edges_total", "edges",
                      tenant="t0").inc(len(xs))
        child.histogram("repro_publish_latency_seconds", "lat",
                        tenant="t0").observe_many(xs)
        parent.adopt(f"worker:{name}", child.state())
    assert sorted(parent.adopted_sources()) == ["worker:w1", "worker:w2"]

    merged = parent.merged_state()
    all_xs = np.concatenate(list(child_samples.values()))
    (hist_state,) = [h for n, _, h in merged["hists"]
                     if n == "repro_publish_latency_seconds"]
    assert hist_state["count"] == len(all_xs)
    assert hist_state["sum"] == pytest.approx(float(all_xs.sum()), abs=1e-9)
    oracle = Histogram("o", {})
    oracle.observe_many(all_xs)
    assert hist_state["counts"] == oracle.state()["counts"]

    samples = parse_prometheus_text(render_prometheus(merged))
    key = ("repro_publish_latency_seconds_sum", (("tenant", "t0"),))
    assert samples[key] == float(hist_state["sum"])  # exact round-trip
    cnt = samples[("repro_publish_latency_seconds_count",
                   (("tenant", "t0"),))]
    assert cnt == len(all_xs)
    edges = samples[("repro_ingest_edges_total", (("tenant", "t0"),))]
    assert edges == sum(len(x) for x in child_samples.values())
    # +Inf bucket must equal _count (cumulative le semantics)
    inf = samples[("repro_publish_latency_seconds_bucket",
                   (("le", "+Inf"), ("tenant", "t0")))]
    assert inf == cnt

    # re-adopting the SAME source replaces, never double-counts
    parent.adopt("worker:w1", parent._adopted["worker:w1"])
    again = parent.merged_state()
    (h2,) = [h for n, _, h in again["hists"]
             if n == "repro_publish_latency_seconds"]
    assert h2["count"] == len(all_xs)


def test_prometheus_parser_is_strict():
    assert parse_prometheus_text("# HELP x y\n# TYPE x counter\nx 1\n") == {
        ("x", ()): 1.0}
    with pytest.raises(ValueError):
        parse_prometheus_text("not a metric line at all")
    with pytest.raises(ValueError):
        parse_prometheus_text('x{bad-label="1"} 2')


# ---------------------------------------------------- runtime satellites


def test_rate_ewma_folds_first_sample_into_next_interval():
    """Satellite: the first update's count must not vanish — it seeds the
    next interval's numerator."""
    r = RateEWMA(halflife_s=5.0)
    r.update(1000, now=100.0)
    assert r.rate == 0.0  # no interval yet — but the count is carried...
    r.update(1000, now=101.0)
    # ...so the first measurable instant rate is 2000/s, not 1000/s
    assert r.rate > RateEWMA(halflife_s=5.0).rate
    two = RateEWMA(halflife_s=5.0)
    two.update(0, now=100.0)
    two.update(1000, now=101.0)
    assert r.rate == pytest.approx(two.rate * 2.0)


def test_worker_metrics_lifetime_wall_is_first_ingest():
    """Satellite: edges_per_s_lifetime must wall at first_ingest_at, not
    started_at — spawn/compile warmup is not ingest time."""
    m = WorkerMetrics(started_at=0.0)
    qs = {"depth": 0, "dropped_batches": 0, "dropped_edges": 0,
          "spilled_batches": 0, "max_depth_seen": 0}
    assert m.snapshot(queue_stats=qs, state="running", epoch=0,
                      now=50.0)["edges_per_s_lifetime"] == 0.0
    m.note_ingest(1000, now=100.0)  # 100s of warmup before this
    m.note_ingest(1000, now=102.0)
    snap = m.snapshot(queue_stats=qs, state="running", epoch=0, now=102.0)
    assert snap["edges_per_s_lifetime"] == pytest.approx(1000.0, rel=0.01)


def test_worker_metrics_bind_hub_mirrors_typed_instruments():
    m = WorkerMetrics(started_at=0.0)
    m.bind_hub("tenantX", backend="thread")
    m.note_ingest(512, now=1.0)
    m.note_ingest(256, now=2.0)
    m.note_publish(0.05, now=2.5)
    state = get_hub().state()
    counters = {(n, tuple(sorted(l.items()))): v
                for n, l, v in state["counters"]}
    labels = (("backend", "thread"), ("tenant", "tenantX"))
    assert counters[("repro_ingest_edges_total", labels)] == 768
    assert counters[("repro_ingest_batches_total", labels)] == 2
    (batch_h,) = [h for n, _, h in state["hists"]
                  if n == "repro_ingest_batch_edges"]
    assert batch_h["count"] == 2 and batch_h["ladder"] == "size"


# ------------------------------------------------------------ trace spans


def test_thread_runtime_closes_ingest_chains_with_edge_parity():
    from repro.runtime import Runtime

    reg = _registry()
    t = reg.open("cit-HepPh", "kmatrix", 64, seed=0)
    rt = Runtime(publish_policy="drain:0", reservoir_k=0, backend="thread")
    rt.attach(t)
    rt.start(pumps=False)
    rt.wait_ready()
    rt.start_pumps()
    rt.join_pumps()
    rep = rt.stop(drain=True)[t.key.tenant_id]
    assert rep["unaccounted_edges"] == 0

    state = get_hub().merged_state()
    edges = [v for n, _, v in state["counters"]
             if n == "repro_ingest_edges_total"]
    assert sum(edges) == rep["ingested_edges"]

    chains = {}
    for e in get_trace_log().events():
        chains.setdefault(e["trace"], []).append(e["event"])
    closed = [c for c in chains.values()
              if {"enqueue", "dispatch", "publish"} <= set(c)]
    assert closed, f"no closed thread ingest chain in {chains}"


def test_socket_runtime_adopts_worker_hub_and_closes_chains():
    """Tentpole gate over real TCP: the parent's merged hub equals the
    socket child's counters (adopted, never double-counted) and a batch's
    chain closes enqueue -> dispatch -> publish -> adopt across the
    process+socket boundary."""
    from repro.runtime import Runtime

    reg = _registry()
    t = reg.open("cit-HepPh", "kmatrix", 64, seed=0)
    rt = Runtime(publish_policy="drain:0", reservoir_k=0, backend="socket")
    rt.attach(t)
    rt.start(pumps=False)
    rt.wait_ready()
    rt.start_pumps()
    rt.join_pumps()
    rep = rt.stop(drain=True)[t.key.tenant_id]
    assert rep["unaccounted_edges"] == 0

    hub = get_hub()
    assert any(s.startswith("worker:") for s in hub.adopted_sources())
    state = hub.merged_state()
    edges = [v for n, _, v in state["counters"]
             if n == "repro_ingest_edges_total"]
    assert sum(edges) == rep["ingested_edges"]

    chains = {}
    for e in get_trace_log().events():
        chains.setdefault(e["trace"], []).append(e["event"])
    closed = [c for c in chains.values()
              if {"enqueue", "dispatch", "publish", "adopt"} <= set(c)]
    assert closed, f"no closed socket ingest chain in {chains}"


def test_query_server_traces_and_scrape_match_ledger():
    """A query's accept -> plan -> execute -> reply chain closes, and the
    scraped exposition mirrors the admission ledger exactly."""
    from repro.net.query_server import QueryClient, QueryServer

    snap = types.SimpleNamespace(epoch=3, n_edges=10)
    eng = types.SimpleNamespace(execute=lambda s, reqs: [
        types.SimpleNamespace(epoch=s.epoch, value=0.0) for _ in reqs])
    server = QueryServer(eng, lambda: snap).start()
    try:
        client = QueryClient(server.address)
        for _ in range(3):
            assert client.call(["q1", "q2"])["kind"] == "result"
        payload = client.metrics()
        client.close()
    finally:
        server.stop()

    samples = parse_prometheus_text(payload["prometheus"])
    assert samples[("repro_query_served_requests_total", ())] == 6
    assert samples[("repro_query_offered_requests_total", ())] == 6
    (lat,) = [h for n, _, h in payload["state"]["hists"]
              if n == "repro_query_latency_seconds"]
    assert lat["count"] == 6  # one observation per served request

    chains = {}
    for e in get_trace_log().events():
        if e["span"] == "query":
            chains.setdefault(e["trace"], []).append(e["event"])
    assert chains and all(
        c == ["accept", "plan", "execute", "reply"] for c in chains.values())


def test_trace_log_is_bounded_and_dumps_jsonl(tmp_path):
    log = get_trace_log()
    for i in range(5000):
        log.emit(f"t{i}", "ingest", "enqueue", offset=i)
    assert len(log.events()) == 4096  # bounded ring, oldest dropped
    path = tmp_path / "spans.jsonl"
    n = log.dump_jsonl(str(path))
    lines = path.read_text().splitlines()
    assert n == len(lines) == 4096
    rec = json.loads(lines[-1])
    assert rec["event"] == "enqueue" and rec["offset"] == 4999


# ------------------------------------------------------- exposition surface


def test_metrics_frame_requires_auth_on_query_server(monkeypatch):
    """Satellite: the scrape honors --auth-token exactly like query frames
    — telemetry names tenants and throughput, it is not public."""
    from repro.net import wire
    from repro.net.query_server import QueryClient, QueryServer

    monkeypatch.delenv(wire.AUTH_TOKEN_ENV, raising=False)
    snap = types.SimpleNamespace(epoch=1, n_edges=5)
    eng = types.SimpleNamespace(execute=lambda s, reqs: [])
    server = QueryServer(eng, lambda: snap, auth_token="sekrit").start()
    try:
        conn = socket.create_connection(server.address, timeout=10)
        wire.send_message(conn, ("metrics_req",))  # no auth frame
        reply = None
        deadline = time.monotonic() + 30
        while reply is None and time.monotonic() < deadline:
            try:
                reply = wire.recv_message(conn, poll_s=0.2)
            except (ConnectionError, OSError):
                break
        conn.close()
        assert reply is None or reply[0] == "error"

        good = QueryClient(server.address, auth_token="sekrit")
        payload = good.metrics()
        good.close()
        parse_prometheus_text(payload["prometheus"])
    finally:
        server.stop()
    assert server.stats()["auth_failures"] >= 1


def test_metrics_frame_requires_auth_on_worker_server(monkeypatch):
    from repro.net import wire
    from repro.net.ingest_server import WorkerServer

    monkeypatch.delenv(wire.AUTH_TOKEN_ENV, raising=False)
    get_hub().counter("repro_ingest_edges_total", "edges", tenant="x").inc(9)
    server = WorkerServer("127.0.0.1", 0, auth_token="sekrit",
                          hello_timeout_s=10.0)
    host, port = server.address
    thread = threading.Thread(
        target=lambda: server.serve_forever(max_sessions=2), daemon=True)
    thread.start()
    try:
        conn = socket.create_connection((host, port), timeout=10)
        wire.send_message(conn, ("metrics_req",))  # no auth: refused
        deadline = time.monotonic() + 60
        while server.sessions_served < 1 and time.monotonic() < deadline:
            time.sleep(0.02)
        conn.close()
        assert "auth" in server.session_results[0]

        conn2 = socket.create_connection((host, port), timeout=10)
        wire.send_message(conn2, ("auth", "sekrit"))
        wire.send_message(conn2, ("metrics_req",))
        reply = None
        deadline = time.monotonic() + 30
        while reply is None and time.monotonic() < deadline:
            reply = wire.recv_message(conn2, poll_s=0.2)
        conn2.close()
        assert reply is not None and reply[0] == "metrics"
        samples = parse_prometheus_text(reply[1]["prometheus"])
        assert samples[("repro_ingest_edges_total", (("tenant", "x"),))] == 9
        deadline = time.monotonic() + 60
        while server.sessions_served < 2 and time.monotonic() < deadline:
            time.sleep(0.02)
        assert server.session_results[1] == "scraped"
    finally:
        server.stop()
        thread.join(timeout=30)


def test_metrics_json_dumper_and_dashboard_once(tmp_path):
    from repro.obs.dashboard import main as dash_main

    get_hub().counter("repro_ingest_edges_total", "edges",
                      tenant="t").inc(42)
    path = str(tmp_path / "metrics.json")
    dumper = MetricsJsonDumper(path, interval_s=0.05)
    dumper.start()
    time.sleep(0.15)
    dumper.stop()
    assert dumper.writes >= 3
    payload = json.loads((tmp_path / "metrics.json").read_text())
    assert set(payload) == {"prometheus", "state", "ts"}
    assert not os.path.exists(path + ".tmp")  # atomic replace, no litter
    assert dash_main(["--json", path, "--once"]) == 0
    assert dash_main(["--json", str(tmp_path / "absent.json"),
                      "--once"]) == 1


# ------------------------------------------------------------ host spans


def test_span_ring_keeps_end_order_bounds_and_counts_drops(monkeypatch):
    from repro.obs import trace

    monkeypatch.setattr(trace, "SPAN_CAPACITY", 8)
    log = trace.TraceLog()
    for i in range(11):
        with log.span("kmatrix.test.phase", key=i):
            pass
    spans = log.spans()
    assert [s.key for s in spans] == list(range(3, 11))  # oldest dropped
    assert log.spans_dropped == 3
    assert all(s.t0_ns <= s.t1_ns for s in spans)
    assert [s.t1_ns for s in spans] == sorted(s.t1_ns for s in spans)
    log.clear()
    assert log.spans() == [] and log.spans_dropped == 0


def test_nested_spans_share_a_key_and_nest_in_time():
    log = get_trace_log()
    with log.span("kmatrix.test.outer", key=7):
        time.sleep(0.001)
        with log.span("kmatrix.test.inner", key=7):
            time.sleep(0.001)
    with log.span("kmatrix.test.bare"):
        pass
    inner, outer, bare = log.spans()  # appended as each span ends
    assert (inner.name, outer.name) == ("kmatrix.test.inner",
                                        "kmatrix.test.outer")
    assert inner.key == outer.key == 7 and bare.key is None
    assert outer.t0_ns <= inner.t0_ns <= inner.t1_ns <= outer.t1_ns
    assert inner.t1_ns - inner.t0_ns >= 1_000_000
    assert outer.thread == inner.thread == threading.current_thread().name


def test_span_survives_an_exception_in_its_body():
    log = get_trace_log()
    with pytest.raises(ValueError):
        with log.span("kmatrix.test.raises", key=3):
            raise ValueError("boom")
    assert [s.name for s in log.spans()] == ["kmatrix.test.raises"]


def test_span_log_dumps_events_and_spans_as_jsonl(tmp_path):
    log = get_trace_log()
    log.emit("t1", "ingest", "enqueue", offset=0)
    with log.span("kmatrix.test.phase", key=5):
        pass
    path = tmp_path / "spans.jsonl"
    assert log.dump_jsonl(str(path)) == 2
    event, span = [json.loads(line)
                   for line in path.read_text().splitlines()]
    assert event["trace"] == "t1" and "t0_ns" not in event
    assert span["name"] == "kmatrix.test.phase" and span["key"] == 5
    assert span["t1_ns"] >= span["t0_ns"] and "trace" not in span
    assert span["thread"] == threading.current_thread().name


def test_span_lands_in_a_profiler_trace(tmp_path):
    import glob

    import jax
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        with get_trace_log().span("kmatrix.test.profiled", key=1):
            time.sleep(0.002)
    finally:
        jax.profiler.stop_trace()
    files = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    data = ProfileData.from_file(files[0])
    names = {e.name for plane in data.planes for line in plane.lines
             for e in line.events}
    assert "kmatrix.test.profiled" in names
    assert [s.name for s in get_trace_log().spans()] == [
        "kmatrix.test.profiled"]


@pytest.mark.parametrize("dedup", [True, False])
def test_thread_worker_spans_key_each_dispatch_and_each_epoch(dedup):
    """One thread-runtime ingest: every dispatch's spans share its sequence
    number and run in order, and every publish's span and its child carry
    the epoch it produced.  The child is the device sync without dedup,
    and the host-count read with it (every dedup dispatch carries its
    count, so no publish waits on the device)."""
    from repro.runtime import Runtime

    reg = _registry()
    t = reg.open("cit-HepPh", "kmatrix", 64, seed=0)
    rt = Runtime(publish_policy="every:2", dedup=dedup, backend="thread")
    rt.attach(t)
    rt.start(pumps=False)
    rt.wait_ready()
    rt.start_pumps()
    rt.join_pumps()
    assert rt.stop(drain=True)[t.key.tenant_id]["unaccounted_edges"] == 0

    by: dict = {}
    for s in get_trace_log().spans():
        by.setdefault(s.name, []).append(s)
    worker = {s.thread for name, ss in by.items()
              if name.startswith("kmatrix.worker.") for s in ss}
    assert len(worker) == 1
    keys = sorted(s.key for s in by["kmatrix.worker.dispatch"])
    assert keys == list(range(len(keys))) and len(keys) >= 4
    phases = ["stage", "dispatch", "reservoir"]
    if dedup:
        phases.insert(0, "dedup")
    else:
        assert "kmatrix.worker.dedup" not in by
    one = {p: {s.key: s for s in by[f"kmatrix.worker.{p}"]} for p in phases}
    for p in phases:
        assert sorted(one[p]) == keys, p  # once per dispatch
    gets = by["kmatrix.worker.queue_get"]
    assert set(keys) <= {s.key for s in gets} <= set(keys) | {len(keys)}
    for k in keys:
        got = max(s.t1_ns for s in gets if s.key == k)
        ends = [got] + [x for p in phases
                        for x in (one[p][k].t0_ns, one[p][k].t1_ns)]
        assert ends == sorted(ends), k  # queue_get, then each phase in turn

    pubs = {s.key: s for s in by["kmatrix.worker.publish"]}
    assert sorted(pubs) == list(range(1, t.epoch + 1))
    child, other = "publish_sync", "publish_host_count"
    if dedup:
        child, other = other, child
    assert not [s for s in by.get(f"kmatrix.snapshot.{other}", [])
                if s.thread in worker]
    syncs = {s.key: s for s in by[f"kmatrix.snapshot.{child}"]
             if s.thread in worker}
    assert sorted(syncs) == sorted(pubs)
    for epoch, sync in syncs.items():
        assert pubs[epoch].t0_ns <= sync.t0_ns <= sync.t1_ns \
            <= pubs[epoch].t1_ns


def test_loadgen_reports_carry_merged_histogram_summary(rng):
    """Satellite: LoadReport/NetLoadReport expose p90/p99.9 and a summary
    sourced from the mergeable histograms."""
    from repro.serving.loadgen import LoadReport, _latency_summary_ms

    h = Histogram("l", {})
    xs = rng.exponential(0.005, 1000)
    h.observe_many(xs)
    s = _latency_summary_ms(h.state())
    assert s["count"] == 1000
    assert s["p50"] <= s["p90"] <= s["p99"] <= s["p999"] <= s["max"]
    # the summary rounds to 4 decimals (report hygiene), so compare there
    assert s["mean"] == pytest.approx(float(xs.mean()) * 1e3, abs=1e-3)

    fields = {f.name for f in LoadReport.__dataclass_fields__.values()}
    assert {"p90_ms", "p999_ms", "latency_hist"} <= fields
    rep = LoadReport(n_requests=1, duration_s=1.0, offered_qps=1.0,
                     achieved_qps=1.0, p50_ms=1.0, p90_ms=2.0, p99_ms=3.0,
                     p999_ms=4.0, mean_ms=1.5, max_ms=4.0, n_batches=1,
                     family_counts={}, latency_hist=s)
    parsed = json.loads(rep.to_json())
    assert parsed["latency_hist"]["count"] == 1000
