"""Device-resident ingest fast path (ISSUE 10): buffer donation,
exact duplicate-edge pre-aggregation, and pipelined dispatch.

The contract under test is *bit-exactness*: every fast-path arm
(donation on/off x dedup on/off) must publish counters, pending ledgers,
and estimates identical to the plain path — donation because the kernels
are alias-safe rewrites, dedup because sketch counters are linear in the
update stream (int32 wrap-add is associative and commutative).
"""
import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import countmin, kmatrix, kmatrix_accel
from repro.core.types import EdgeBatch
from repro.runtime import QueueItem, Runtime
from repro.runtime.worker import IngestWorker, _item_nbytes, preaggregate_edges
from repro.serving import SketchRegistry
from repro.serving.gates import layout_counters_equal
from repro.serving.snapshot import SnapshotBuffer, donation_enabled


def _registry(**kw):
    kw.setdefault("depth", 3)
    kw.setdefault("batch_size", 1024)
    kw.setdefault("scale", 0.02)
    return SketchRegistry(**kw)


def _random_edges(rng, n, n_nodes=200, wrap=False):
    src = rng.integers(-5, n_nodes, n).astype(np.int32)
    dst = rng.integers(-5, n_nodes, n).astype(np.int32)
    if wrap:
        w = rng.integers(-(2 ** 31), 2 ** 31, n, dtype=np.int64) \
            .astype(np.int32)
    else:
        w = rng.integers(-3, 4, n).astype(np.int32)
    return src, dst, w


def _oracle(src, dst, w):
    """Wrap-accurate int32 per-(src, dst) sums, zero-weight rows dropped."""
    acc = {}
    for s, d, x in zip(src.tolist(), dst.tolist(), w.tolist()):
        if x == 0:
            continue
        k = (s, d)
        v = (acc.get(k, 0) + x) & 0xFFFFFFFF
        acc[k] = v
    out = {k: v - (1 << 32) if v >= (1 << 31) else v
           for k, v in acc.items()}
    return {k: v for k, v in out.items() if v != 0}


# -------------------------------------------------------- pre-aggregation
@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("wrap", [False, True])
def test_preaggregate_matches_wraparound_oracle(seed, wrap):
    """Randomized bit-exactness incl. negative weights (turnstile), heavy
    duplicates, negative node ids, and int32 wrap-add."""
    rng = np.random.default_rng(seed)
    src, dst, w = _random_edges(rng, 4096, n_nodes=64, wrap=wrap)
    us, ud, uw = preaggregate_edges(src, dst, w)
    got = dict(zip(zip(us.tolist(), ud.tolist()), uw.tolist()))
    assert got == _oracle(src, dst, w)
    # unique keys, no zero weights in the output
    assert len(got) == us.shape[0]
    assert np.all(uw != 0)


def test_preaggregate_drops_cancelled_and_zero_rows():
    src = np.array([1, 1, 2, 3], np.int32)
    dst = np.array([9, 9, 8, 7], np.int32)
    w = np.array([3, -3, 0, 5], np.int32)
    us, ud, uw = preaggregate_edges(src, dst, w)
    assert us.tolist() == [3] and ud.tolist() == [7] and uw.tolist() == [5]


def test_preaggregated_ingest_is_bit_identical_on_countmin():
    """Counter linearity, end to end: raw batch vs its pre-aggregate land
    in identical sketches."""
    rng = np.random.default_rng(7)
    src, dst, w = _random_edges(rng, 2048, n_nodes=50)
    sk_raw = countmin.CountMin.create(bytes_budget=4096, depth=3, seed=1)
    sk_agg = countmin.CountMin.create(bytes_budget=4096, depth=3, seed=1)
    sk_raw = countmin.ingest(sk_raw, EdgeBatch.from_numpy(src, dst, w))
    us, ud, uw = preaggregate_edges(src, dst, w)
    sk_agg = countmin.ingest(sk_agg, EdgeBatch.from_numpy(us, ud, uw))
    np.testing.assert_array_equal(np.asarray(sk_raw.table),
                                  np.asarray(sk_agg.table))


# ----------------------------------------------------------- byte ledger
def test_coalesce_byte_ledger_uses_actual_column_dtypes():
    """The cap ledger derives bytes from the item's real dtypes — an int64
    weight column costs 16 B/row, not the int32-era hardcoded 12."""
    n = 100
    item32 = QueueItem.from_arrays(
        0, np.ones(n, np.int32), np.ones(n, np.int32), np.ones(n, np.int32))
    item64 = QueueItem.from_arrays(
        1, np.ones(n, np.int32), np.ones(n, np.int32), np.ones(n, np.int64))
    assert _item_nbytes(item32) == n * 12
    assert _item_nbytes(item64) == n * 16


# ---------------------------------------------------------------- donation
def _feed(buf, batches):
    for src, dst, w in batches:
        buf.ingest(EdgeBatch.from_numpy(src, dst, w))


def _batches(seed, k=6, n=512):
    rng = np.random.default_rng(seed)
    return [_random_edges(rng, n, n_nodes=100) for _ in range(k)]


def test_donation_kill_switch_parity_countmin():
    """donate=True and donate=False buffers publish bit-identical fronts,
    pending ledgers, and estimates across multiple publish rounds."""
    sk = countmin.CountMin.create(bytes_budget=8192, depth=3, seed=2)
    bufs = {d: SnapshotBuffer(jax.tree_util.tree_map(jnp.array, sk),
                              countmin, tenant_id="t", donate=d)
            for d in (False, True)}
    assert bufs[True].donate or not donation_enabled()
    batches = _batches(3)
    for i in range(3):
        for d, buf in bufs.items():
            _feed(buf, batches[i * 2:(i + 1) * 2])
            buf.publish()
    a, b = bufs[False].snapshot, bufs[True].snapshot
    assert a.n_edges == b.n_edges and a.epoch == b.epoch
    assert layout_counters_equal(a.sketch, b.sketch)
    q = np.arange(64, dtype=np.int32)
    np.testing.assert_array_equal(
        np.asarray(countmin.edge_freq(a.sketch, q, q[::-1].copy())),
        np.asarray(countmin.edge_freq(b.sketch, q, q[::-1].copy())))


def test_donation_checkpoint_restore_roundtrip():
    """state() under donation hands out private copies that survive later
    donating dispatches, and a buffer restored from it converges to the
    same front as the uninterrupted one."""
    sk = countmin.CountMin.create(bytes_budget=8192, depth=3, seed=4)
    buf = SnapshotBuffer(sk, countmin, tenant_id="t", donate=True)
    batches = _batches(5, k=4)
    _feed(buf, batches[:2])
    state = buf.state()
    saved_delta = jax.tree_util.tree_map(np.asarray, state["delta"])
    saved_pending = int(np.asarray(state["pending"]))

    # keep ingesting + publishing on the live buffer: if state() aliased
    # the live delta, these donations would delete the saved leaves
    _feed(buf, batches[2:])
    buf.publish()
    for a, b in zip(jax.tree_util.tree_leaves(saved_delta),
                    jax.tree_util.tree_leaves(
                        jax.tree_util.tree_map(np.asarray, state["delta"]))):
        np.testing.assert_array_equal(a, b)
    assert int(np.asarray(state["pending"])) == saved_pending

    sk2 = countmin.CountMin.create(bytes_budget=8192, depth=3, seed=4)
    buf2 = SnapshotBuffer(sk2, countmin, tenant_id="t", donate=True)
    buf2.load_state(state)
    _feed(buf2, batches[2:])
    buf2.publish()
    assert buf2.snapshot.n_edges == buf.snapshot.n_edges
    assert layout_counters_equal(buf2.snapshot.sketch, buf.snapshot.sketch)


def test_donated_buffer_capture_publish_delta_stays_readable():
    """capture_publish_delta forces the never-donating publish kernel, so
    the stashed delta survives the publish that folded it in."""
    sk = countmin.CountMin.create(bytes_budget=4096, depth=3, seed=5)
    buf = SnapshotBuffer(sk, countmin, tenant_id="t", donate=True)
    buf.capture_publish_delta = True
    for batches in (_batches(6, k=2), _batches(7, k=2)):
        _feed(buf, batches)
        buf.publish()
        total = sum(int(np.asarray(x).sum())
                    for x in jax.tree_util.tree_leaves(
                        buf.last_publish_delta)
                    if np.issubdtype(np.asarray(x).dtype, np.integer))
        assert isinstance(total, int)  # readable, not deleted


# ----------------------------------------------- publish's pending count
def _live(batch):
    return int(np.count_nonzero(batch[2] > 0))


def _publish_counting_device_gets(buf, monkeypatch):
    """``buf.publish()`` with ``jax.device_get`` counted while it runs."""
    calls = []
    real = jax.device_get

    def counting(x):
        calls.append(x)
        return real(x)

    monkeypatch.setattr(jax, "device_get", counting)
    try:
        snap = buf.publish()
    finally:
        monkeypatch.setattr(jax, "device_get", real)
    return snap, len(calls)


def _counted_only(buf, monkeypatch):
    batches = _batches(11)
    for b in batches:
        buf.ingest(EdgeBatch.from_numpy(*b), count=_live(b))
    snap, gets = _publish_counting_device_gets(buf, monkeypatch)
    assert gets == 0  # stamped from the host's own count
    return snap, sum(_live(b) for b in batches)


def _one_uncounted(buf, monkeypatch):
    batches = _batches(12)
    for i, b in enumerate(batches):
        if i == 3:
            buf.ingest(EdgeBatch.from_numpy(*b))  # only the device counts it
        else:
            buf.ingest(EdgeBatch.from_numpy(*b), count=_live(b))
    snap, gets = _publish_counting_device_gets(buf, monkeypatch)
    assert gets == 1  # falls back to the device count
    # and the next epoch, counted only, is back on the host count
    more = _batches(13, k=2)
    for b in more:
        buf.ingest(EdgeBatch.from_numpy(*b), count=_live(b))
    snap2, gets = _publish_counting_device_gets(buf, monkeypatch)
    assert gets == 0
    assert snap2.n_edges - snap.n_edges == sum(_live(b) for b in more)
    return snap, sum(_live(b) for b in batches)


def _after_load_state(buf, monkeypatch):
    src_buf = SnapshotBuffer(buf.snapshot.sketch, countmin, tenant_id="s",
                             donate=buf.donate)
    first, rest = _batches(14, k=3), _batches(15, k=3)
    _feed(src_buf, first[:1])  # uncounted: the checkpoint's pending
    src_buf.publish()
    _feed(src_buf, first[1:])
    buf.load_state(src_buf.state())
    for b in rest:
        buf.ingest(EdgeBatch.from_numpy(*b), count=_live(b))
    snap, gets = _publish_counting_device_gets(buf, monkeypatch)
    assert gets == 0  # the restore read the pending count already
    return snap, sum(_live(b) for b in first + rest)


def _dedup_against_plain_runtime(buf, monkeypatch):
    from repro.obs.trace import reset_trace_log

    runs = {}
    for dedup in (False, True):
        log = reset_trace_log()
        snap, _ = _run_runtime(dedup=dedup)
        runs[dedup] = snap, {s.name for s in log.spans()
                             if s.name.startswith("kmatrix.snapshot.")}
    reset_trace_log()
    (base, base_paths), (fast, fast_paths) = runs[False], runs[True]
    assert base_paths == {"kmatrix.snapshot.publish_sync"}
    assert fast_paths == {"kmatrix.snapshot.publish_host_count"}
    assert layout_counters_equal(fast.sketch, base.sketch)
    assert fast.epoch >= 1
    return fast, base.n_edges


@pytest.mark.parametrize("case", [_counted_only, _one_uncounted,
                                  _after_load_state,
                                  _dedup_against_plain_runtime],
                         ids=lambda f: f.__name__.strip("_"))
def test_publish_stamps_exact_n_edges_from_the_host_count(case, monkeypatch):
    """``publish`` stamps ``n_edges`` from the host's own count while every
    ingest since the previous publish carried one, and from the device
    count otherwise; either way the count is exact."""
    sk = countmin.CountMin.create(bytes_budget=4096, depth=3, seed=9)
    buf = SnapshotBuffer(sk, countmin, tenant_id="t")
    snap, want = case(buf, monkeypatch)
    assert snap.n_edges == want


# ------------------------------------------------- runtime fast-path A/B
def _run_runtime(dataset="email-EuAll", *, dedup, backend="thread",
                 max_batches=12, **rt_kw):
    reg = _registry(scale=0.05)
    t = reg.open(dataset, "kmatrix", 64, seed=7)
    rt = Runtime(publish_policy="drain:0", reservoir_k=0,
                 coalesce_batches=4, coalesce_target=4096,
                 dedup=dedup, backend=backend, **rt_kw)
    rt.attach(t, max_batches=max_batches)
    rt.start(pumps=False)
    assert rt.wait_ready(300)
    rt.start_pumps()
    assert rt.join_pumps(300)
    rep = rt.stop(drain=True)[t.key.tenant_id]
    assert rep["unaccounted_edges"] == 0
    return t.snapshot, rep


def test_dedup_runtime_bit_identical_and_counts_compression():
    """Thread-backend A/B: the dedup arm publishes the same counters and
    pending totals as the plain coalesced path, and reports its
    compression through the metrics surface."""
    base, rep0 = _run_runtime(dedup=False)
    fast, rep1 = _run_runtime(dedup=True)
    assert fast.n_edges == base.n_edges
    assert layout_counters_equal(fast.sketch, base.sketch)
    assert rep0.get("dedup_ratio") is None
    assert rep1["dedup_ratio"] >= 1.0
    assert rep1["dedup_unique_rows"] <= rep1["dedup_raw_rows"]


@pytest.fixture(scope="module")
def accel_groups():
    """A small width-class sketch over skewed email-EuAll (banded plan)
    three coalesced groups of its stream, as the worker builds them, and
    the plain arm's snapshot (no donation, no dedup)."""
    from repro.core import KMatrixAccel, vertex_stats_from_sample
    from repro.streams import make_stream, sample_stream

    stream = make_stream("email-EuAll", batch_size=1024, seed=5, scale=0.05)
    stats = vertex_stats_from_sample(*sample_stream(stream, 3000, seed=7))
    sk = KMatrixAccel.create(bytes_budget=64 * 1024, stats=stats, depth=3,
                             seed=3, partitioner="banded")
    groups = []
    for g in range(3):
        cols = [stream.batch_numpy(4 * g + k) for k in range(4)]
        groups.append(tuple(np.ascontiguousarray(
            np.concatenate([c[j] for c in cols]), np.int32)
            for j in range(3)))
    plain = _accel_arm(sk, groups, donate=False, dedup=False)
    return sk, groups, plain


def _accel_arm(sk, groups, *, donate, dedup):
    buf = SnapshotBuffer(sk, kmatrix_accel, tenant_id="t", donate=donate)
    assert buf.donate == donate
    for src, dst, w in groups:
        if dedup:
            us, ud, uw = preaggregate_edges(src, dst, w)
            assert us.shape[0] < src.shape[0]  # the stream has duplicates
            # zero-weight padding to the raw length: one dispatch shape
            pad = [np.zeros_like(src) for _ in range(3)]
            for col, part in zip(pad, (us, ud, uw)):
                col[:part.shape[0]] = part
            buf.ingest(EdgeBatch.from_numpy(*pad),
                       count=int(np.count_nonzero(w)))
        else:
            buf.ingest(EdgeBatch.from_numpy(src, dst, w))
    return buf.publish()


@pytest.mark.parametrize("dedup", [False, True])
@pytest.mark.parametrize("donate", [False, True])
def test_width_class_fast_path_arms_are_bit_identical(accel_groups, donate,
                                                      dedup):
    """Donation x dedup on the width-class layout: every arm publishes the
    counters and ``n_edges`` of the plain arm (no donation, no dedup), and
    the plain arm's are those of an exact scatter of the raw groups."""
    sk, groups, plain = accel_groups
    snap = _accel_arm(sk, groups, donate=donate, dedup=dedup)
    raw = sum(int(np.count_nonzero(g[2])) for g in groups)
    assert snap.n_edges == plain.n_edges == raw
    assert layout_counters_equal(snap.sketch, plain.sketch)
    flat = kmatrix_accel.to_flat_layout(kmatrix_accel.empty_like(sk))
    for g in groups:
        flat = kmatrix.ingest(flat, EdgeBatch.from_numpy(*g))
    assert layout_counters_equal(
        kmatrix_accel.to_flat_layout(plain.sketch), flat)


@pytest.mark.slow
@pytest.mark.parametrize("backend", ["process", "socket"])
def test_remote_backend_dedup_donation_conserves_and_matches(backend):
    """The dedup flag and the donation env both cross the spawn/dial
    boundary (child-spec field + spec.env): a remote-backend drain with
    dedup on stays bit-identical to the in-process plain run."""
    base, _ = _run_runtime(dedup=False)
    fast, rep = _run_runtime(dedup=True, backend=backend,
                             queue_capacity=4, poll_s=0.01)
    assert fast.n_edges == base.n_edges
    assert layout_counters_equal(fast.sketch, base.sketch)


def test_donation_defaults_and_kill_switch_env(monkeypatch):
    monkeypatch.delenv("REPRO_DONATE", raising=False)
    assert donation_enabled()
    for off in ("0", "false", "OFF"):
        monkeypatch.setenv("REPRO_DONATE", off)
        assert not donation_enabled()
    monkeypatch.setenv("REPRO_DONATE", "1")
    assert donation_enabled()
    sk = countmin.CountMin.create(bytes_budget=1024, depth=2, seed=0)
    assert SnapshotBuffer(sk, countmin, tenant_id="t").donate
    monkeypatch.setenv("REPRO_DONATE", "0")
    assert not SnapshotBuffer(sk, countmin, tenant_id="t").donate


@pytest.mark.parametrize("target", [64, 1024, 4096, 8192, 65536])
def test_dispatch_granule_is_the_padding_rule(target, monkeypatch):
    """``dispatch_granule`` is the rule ``max(256, coalesce_target // 4)``
    (which the benchmark's warm-up copies), and the coalesced dispatch pads
    its rows to a multiple of it."""
    from repro.runtime import BoundedEdgeQueue, make_policy

    t = _registry().open("cit-HepPh", "kmatrix", 64, seed=0)
    worker = IngestWorker(t, BoundedEdgeQueue(4), make_policy("every:1"),
                          coalesce_target=target)
    granule = max(256, target // 4)
    assert worker.dispatch_granule() == granule
    rows = []
    monkeypatch.setattr(t.buffer, "ingest",
                        lambda batch, count=None:
                        rows.append(batch.src.shape[0]))
    rng = np.random.default_rng(target)
    for n in (1, granule, granule + 1, 3 * granule - 1):
        src, dst, w = _random_edges(rng, n)
        worker._ingest_coalesced(
            [QueueItem.from_arrays(-1, src, dst, w)], 0.0)
        assert rows[-1] == max(granule, -(-n // granule) * granule)


@pytest.mark.parametrize("weights,one_pass", [
    ([256, 3], True),
    ([-256, 1], True),
    ([1] * 256, True),  # 256 repeats of one edge: pre-aggregated to 256
    ([257, 3], False),
    ([-257, 1], False),
    ([1] * 257, False),
], ids=["256", "-256", "dup-256", "257", "-257", "dup-257"])
def test_one_pass_dispatches_counts_the_kernel_rule(weights, one_pass,
                                                    monkeypatch, request):
    """A dedup dispatch counts as one-pass exactly when every pre-aggregated
    weight lies within ``ONE_PASS_MAX_WEIGHT`` (256) of zero, and the count
    reaches the snapshot and the hub's exposition."""
    from repro.obs.hub import get_hub
    from repro.runtime import BoundedEdgeQueue, make_policy

    t = _registry().open("cit-HepPh", "kmatrix", 64, seed=0)
    worker = IngestWorker(t, BoundedEdgeQueue(4), make_policy("every:1"),
                          dedup=True)
    worker.metrics.bind_hub(request.node.name)  # labels no other test uses
    monkeypatch.setattr(t.buffer, "ingest", lambda batch, count=None: None)
    w = np.asarray(weights, np.int32)
    repeats = w.size > 2
    src = np.zeros(w.size, np.int32) if repeats else np.arange(w.size,
                                                              dtype=np.int32)
    dst = np.full(w.size, 9, np.int32)
    worker._ingest_coalesced([QueueItem.from_arrays(-1, src, dst, w)], 0.0)
    snap = worker.metrics_snapshot()
    assert snap["dedup_dispatches"] == 1
    assert snap["one_pass_dispatches"] == int(one_pass)
    counters = {n: v for n, labels, v in get_hub().state()["counters"]
                if labels.get("tenant") == request.node.name}
    assert counters["repro_ingest_dedup_dispatches_total"] == 1
    assert counters["repro_ingest_one_pass_dispatches_total"] == \
        int(one_pass)
