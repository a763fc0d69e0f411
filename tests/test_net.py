"""Network transport tier (ISSUE 6 tentpole): the shared wire codec, the
socket execution backend, and the TCP query front-end (DESIGN.md §Net).

Covers the satellites end to end: malformed/truncated frames are loud
``WireError``/``ConnectionError`` (never hangs — the poll/deadline split is
exercised on real sockets), a killed self-hosted socket worker restores
through the manifest with conservation + bit-exactness + engine==direct, a
dead TCP peer surfaces as ``WorkerFailure`` carrying last-known accounting,
admission-control shed is always accounted (offered == admitted + shed on
the server, accepted + shed + errors == offered at the client), and the
remote ``stream_ingest --listen`` placement drains bit-exactly.  The
multi-connection soak is ``slow``-marked for the dedicated CI lane."""
import os
import signal
import socket
import threading
import time
import types

import numpy as np
import jax
import pytest

from repro.core import kmatrix
from repro.net import wire
from repro.runtime import Runtime, WorkerFailure
from repro.serving import (
    QueryEngine,
    ShardedQueryEngine,
    SketchRegistry,
    attach_shards,
    mix_for_sketch,
    read_shard_manifest,
    sharded_conservation,
    sharded_direct_answers,
    synth_requests,
)
from repro.serving.gates import values_match


def _registry(**kw):
    kw.setdefault("depth", 3)
    kw.setdefault("batch_size", 1024)
    kw.setdefault("scale", 0.02)
    return SketchRegistry(**kw)


def _single_shot(dataset="cit-HepPh", kind="kmatrix", budget_kb=64, seed=0):
    reg = _registry()
    t = reg.open(dataset, kind, budget_kb, seed=seed)
    sk = t.snapshot.sketch
    ing = jax.jit(kmatrix.ingest)
    for b in t.stream:
        sk = ing(sk, b)
    return t.stream, sk


def _wait(cond, timeout_s=120.0, poll_s=0.01):
    deadline = time.monotonic() + timeout_s
    while not cond():
        if time.monotonic() >= deadline:
            raise TimeoutError("condition not met in time")
        time.sleep(poll_s)


# ------------------------------------------------------------- wire codec
def test_wire_roundtrip_all_kinds():
    """One codec for pipe and socket: every frame kind round-trips, numpy
    leaves included, byte-for-byte through encode/decode."""
    arr = np.arange(6, dtype=np.int32)
    for msg in [
        ("hello", {"tenant_id": "t", "nested": [1, 2, 3]}),
        ("item", 4, arr, arr + 1, arr * 2, 6),
        ("publish", 3, [arr, arr.astype(np.int64)], 1024, {"m": 1}),
        ("stop", True),
        ("ping",),
    ]:
        out = wire.decode_message(wire.encode_message(msg))
        assert out[0] == msg[0] and len(out) == len(msg)
    got = wire.decode_message(wire.encode_message(("item", 4, arr, arr,
                                                   arr, 6)))
    np.testing.assert_array_equal(got[2], arr)


def test_wire_rejects_malformed_frames_loudly():
    frame = wire.encode_message(("ping",))
    # bad magic: not our stream at all
    with pytest.raises(wire.WireError, match="bad magic"):
        wire.decode_message(b"HTTP" + frame[4:])
    # version skew names both versions
    skew = bytearray(frame)
    skew[5] = 99
    with pytest.raises(wire.WireError, match="version mismatch"):
        wire.decode_message(bytes(skew))
    # unknown frame type
    bad_type = bytearray(frame)
    bad_type[7] = 250
    with pytest.raises(wire.WireError, match="unknown frame type"):
        wire.decode_message(bytes(bad_type))
    # truncated payload: header promises more than arrived
    with pytest.raises(wire.WireError, match="truncated frame"):
        wire.decode_message(frame[:-1])
    # header length field beyond the ceiling
    huge = bytearray(frame)
    huge[8:12] = (wire.MAX_PAYLOAD + 1).to_bytes(4, "big")
    with pytest.raises(wire.WireError, match="exceeds MAX_PAYLOAD"):
        wire.decode_message(bytes(huge))
    # frame type / payload kind disagreement (torn stream)
    pong = wire.encode_message(("pong",))
    franken = frame[:wire.HEADER_SIZE] + pong[wire.HEADER_SIZE:]
    with pytest.raises(wire.WireError, match="frame type says"):
        wire.decode_message(franken)
    # unknown kinds refuse to encode at the sender
    with pytest.raises(wire.WireError, match="unknown wire message kind"):
        wire.encode_message(("warp-drive", 1))
    with pytest.raises(wire.WireError, match="tuples"):
        wire.encode_message(["ping"])


def test_recv_message_poll_deadline_split():
    """Idle peer → None (poll); started-then-stalled frame → TimeoutError;
    peer death mid-frame → ConnectionError.  No path hangs."""
    a, b = socket.socketpair()
    try:
        assert wire.recv_message(b, poll_s=0.05) is None  # idle, not an error
        a.sendall(wire.encode_message(("ping",)))
        assert wire.recv_message(b, poll_s=0.5) == ("ping",)
        frame = wire.encode_message(("stop", True))
        a.sendall(frame[:7])  # a frame STARTS but never finishes
        with pytest.raises(TimeoutError, match="mid-header"):
            wire.recv_message(b, poll_s=0.5, frame_deadline_s=0.3)
    finally:
        a.close()
        b.close()
    a, b = socket.socketpair()
    try:
        frame = wire.encode_message(("stop", True))
        a.sendall(frame[:-2])
        a.close()  # peer dies mid-payload
        with pytest.raises(ConnectionError, match="short read"):
            wire.recv_message(b, poll_s=0.5, frame_deadline_s=5.0)
    finally:
        b.close()
    a, b = socket.socketpair()
    try:
        a.close()  # clean EOF before any frame
        with pytest.raises(ConnectionError, match="closed by peer"):
            wire.recv_message(b, poll_s=0.5)
    finally:
        b.close()


def test_parse_hostport():
    assert wire.parse_hostport("127.0.0.1:80") == ("127.0.0.1", 80)
    for junk in ("nope", ":80", "host:", "host:eighty"):
        with pytest.raises(ValueError, match="HOST:PORT"):
            wire.parse_hostport(junk)


# ----------------------------------------------- v3 columnar item frames
def _cols_item(dtype=np.int32, n=5, n_edges=None, trace="tr-0"):
    rng = np.random.default_rng(3)
    col = lambda: rng.integers(0, 99, n).astype(dtype)  # noqa: E731
    return types.SimpleNamespace(
        offset=7, src=col(), dst=col(), weight=col(),
        n_edges=n if n_edges is None else n_edges, trace_id=trace)


def test_item_cols_roundtrip_across_dtypes():
    """The zero-pickle item path: every allowlisted column dtype round-trips
    exactly, the decode is zero-copy (read-only frombuffer views), and the
    canonical ``("item", ...)`` tuple shape matches the v2 contract."""
    for dtype in (np.int8, np.uint8, np.int32, np.uint32, np.int64,
                  np.float32, np.float64):
        item = _cols_item(dtype=dtype)
        out = wire.decode_message(wire.encode_item_frame(item))
        kind, offset, src, dst, weight, n_edges, trace = out
        assert kind == "item" and offset == 7 and n_edges == 5
        assert trace == "tr-0"
        for got, want in ((src, item.src), (dst, item.dst),
                          (weight, item.weight)):
            assert got.dtype == want.dtype
            np.testing.assert_array_equal(got, want)
            assert not got.flags.writeable  # frombuffer view, not a copy
    # empty batch and empty trace are legal
    empty = _cols_item(n=0, n_edges=0, trace="")
    out = wire.decode_message(wire.encode_item_frame(empty))
    assert out[2].size == 0 and out[5] == 0 and out[6] == ""


def _raw_cols_frame(offset=0, n_edges=2, counts=(2, 2, 2),
                    dtags=(b"<i4", b"<i4", b"<i4"), trace=b"",
                    col_bytes=None):
    """Hand-assemble an ``item_cols`` frame, valid or hostile."""
    if col_bytes is None:
        col_bytes = b"".join(
            np.arange(c, dtype=np.int32).tobytes() for c in counts)
    body = wire._ITEM_COLS.pack(
        offset, n_edges, *counts,
        dtags[0].ljust(8, b"\x00"), dtags[1].ljust(8, b"\x00"),
        dtags[2].ljust(8, b"\x00"), len(trace)) + trace + col_bytes
    return wire._HEADER.pack(wire.MAGIC, wire.WIRE_VERSION,
                             wire.FRAME_TYPES["item_cols"],
                             len(body)) + body


def test_item_cols_malformed_frames_raise_wireerror():
    """Hostile/torn columnar frames die as WireError naming the defect —
    truncation, ragged columns, impossible edge counts, length lies,
    smuggled dtypes, bad trace bytes — never an np.frombuffer crash."""
    ok = _raw_cols_frame()
    assert wire.decode_message(ok)[0] == "item"  # the baseline is valid
    # body shorter than the inner header
    short = wire._HEADER.pack(wire.MAGIC, wire.WIRE_VERSION,
                              wire.FRAME_TYPES["item_cols"], 4) + b"abcd"
    with pytest.raises(wire.WireError, match="truncated columnar"):
        wire.decode_message(short)
    with pytest.raises(wire.WireError, match="ragged"):
        wire.decode_message(_raw_cols_frame(counts=(2, 3, 2)))
    with pytest.raises(wire.WireError, match="non-padding"):
        wire.decode_message(_raw_cols_frame(n_edges=9))
    # header counts promise more column bytes than arrived (oversize lie)
    with pytest.raises(wire.WireError, match="length mismatch"):
        wire.decode_message(_raw_cols_frame(
            counts=(64, 64, 64), n_edges=2, col_bytes=b""))
    # dtype smuggling: object/str dtypes must never reach np.frombuffer
    for tag in (b"|O", b"<U4", b"|V8", b"garbage!"):
        with pytest.raises(wire.WireError,
                           match="disallowed|undecodable"):
            wire.decode_message(_raw_cols_frame(dtags=(tag, b"<i4", b"<i4")))
    with pytest.raises(wire.WireError, match="trace_id"):
        wire.decode_message(_raw_cols_frame(
            trace=b"\xff\xfe", col_bytes=None))
    # encoder refuses what the decoder would refuse
    with pytest.raises(wire.WireError, match="unframeable dtype"):
        wire.encode_item_frame(types.SimpleNamespace(
            offset=0, src=np.array(["a"]), dst=np.zeros(1, np.int32),
            weight=np.zeros(1, np.int32), n_edges=1, trace_id=""))
    with pytest.raises(wire.WireError, match="1-D"):
        wire.encode_item_frame(types.SimpleNamespace(
            offset=0, src=np.zeros((2, 2), np.int32),
            dst=np.zeros(4, np.int32), weight=np.zeros(4, np.int32),
            n_edges=4, trace_id=""))
    with pytest.raises(wire.WireError, match="65535"):
        wire.encode_item_frame(_cols_item(trace="x" * 70000))


def test_v2_item_frames_still_decode():
    """Version compat: a peer still speaking WIRE_VERSION 2 (pickled
    ``item`` tuples) decodes fine — COMPAT_VERSIONS covers the handoff."""
    import pickle

    arr = np.arange(4, dtype=np.int32)
    msg = ("item", 11, arr, arr + 1, arr * 2, 4, "tr-v2")
    payload = pickle.dumps(msg, protocol=pickle.HIGHEST_PROTOCOL)
    frame = wire._HEADER.pack(wire.MAGIC, 2, wire.FRAME_TYPES["item"],
                              len(payload)) + payload
    out = wire.decode_message(frame)
    assert out[0] == "item" and out[1] == 11 and out[6] == "tr-v2"
    np.testing.assert_array_equal(out[2], arr)


def test_leaf_codec_sparse_dense_adaptive_and_exact():
    """Delta leaf codec: sparse leaves ship as COO and reconstruct exactly;
    dense/tiny/scalar leaves ship dense; malformed entries are loud."""
    rng = np.random.default_rng(5)
    sparse = np.zeros((64, 64), np.int64)
    sparse[rng.integers(0, 64, 30), rng.integers(0, 64, 30)] = 7
    dense = rng.integers(1, 9, (16, 16)).astype(np.int32)
    scalar = np.int64(42)
    leaves = [sparse, dense, scalar, np.zeros(0, np.float32)]
    entries = wire.encode_leaves(leaves)
    assert entries[0][0] == "sparse" and entries[1][0] == "dense"
    assert entries[2][0] == "dense" and entries[3][0] == "dense"
    back = wire.decode_leaves(entries)
    for want, got in zip(leaves, back):
        np.testing.assert_array_equal(np.asarray(want), got)
        assert np.asarray(want).dtype == got.dtype
    # sparse must actually be smaller for a sparse leaf
    idx, vals = entries[0][3], entries[0][4]
    assert idx.nbytes + vals.nbytes < sparse.nbytes
    with pytest.raises(wire.WireError, match="unknown leaf encoding"):
        wire.decode_leaves([("mystery", 1)])
    with pytest.raises(wire.WireError, match="do not fit"):
        wire.decode_leaves([("sparse", (2, 2), "<i8",
                             np.array([9], np.uint32),
                             np.array([1], np.int64))])


@pytest.mark.parametrize("backend", ["flat", "pallas"])
def test_delta_publish_frame_is_smaller_and_folds_to_the_front(backend):
    """One publish encoded both ways through the leaf codec: the captured
    per-epoch delta ships in a nonempty frame smaller than the whole front's
    (which is no larger than the raw leaves a full publish ships), and the
    decoded delta merged into the previous front is the published front."""
    t = _registry(sketch_backend=backend).open("cit-HepPh", "kmatrix", 64,
                                               seed=0)
    t.buffer.capture_publish_delta = True
    t.step(4)
    prev = t.publish()
    t.step(1)
    snap = t.publish()
    assert snap.epoch == prev.epoch + 1
    assert hasattr(snap.sketch, "pools") == (backend == "pallas")

    def frame(leaves):
        return wire.encode_message(("publish", {
            "epoch": snap.epoch, "n_edges": snap.n_edges,
            "leaves": wire.encode_leaves(
                [np.asarray(x) for x in leaves])}))

    delta_leaves = jax.tree_util.tree_leaves(t.buffer.last_publish_delta)
    front_leaves, treedef = jax.tree_util.tree_flatten(snap.sketch)
    delta_frame, full_frame = frame(delta_leaves), frame(front_leaves)
    assert 0 < len(delta_frame) < len(full_frame)

    payload = wire.decode_message(delta_frame)[1]
    delta = jax.tree_util.tree_unflatten(
        treedef, [jax.numpy.asarray(x)
                  for x in wire.decode_leaves(payload["leaves"])])
    folded = jax.tree_util.tree_leaves(t.mod.merge(prev.sketch, delta))
    assert len(folded) == len(front_leaves)
    for got, want in zip(folded, front_leaves):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# --------------------------------------------------------- wire security
def test_wire_restricted_unpickler_blocks_code_execution():
    """A crafted frame whose pickle names an executable global (the classic
    ``__reduce__`` RCE gadget) must die as a WireError at decode — never
    reach the interpreter — while every legitimate payload shape (repro
    dataclasses, numpy arrays AND scalars, containers) still round-trips."""
    import subprocess

    class Evil:
        def __reduce__(self):
            return (subprocess.check_output, (["true"],))

    frame = wire.encode_message(("query", {"requests": [Evil()]}))
    with pytest.raises(wire.WireError, match="not allowed"):
        wire.decode_message(frame)

    class EvilEval:
        def __reduce__(self):
            return (eval, ("1+1",))

    with pytest.raises(wire.WireError, match="not allowed"):
        wire.decode_message(wire.encode_message(("query", EvilEval())))

    # the allowlist still admits everything the protocol actually ships
    from repro.serving import engine as eng

    legit = ("query", {"id": 1, "tenant": "t", "requests": [
        eng.edge_freq(1, 2), eng.heavy_nodes(16, 2.0)]})
    out = wire.decode_message(wire.encode_message(legit))
    assert out[1]["requests"][0].family == "edge_freq"
    npy = ("publish", 3, [np.arange(4, dtype=np.int32)], np.int64(7),
           {"m": np.float64(1.5), "d": collections_roundtrip()})
    got = wire.decode_message(wire.encode_message(npy))
    np.testing.assert_array_equal(got[2][0], np.arange(4, dtype=np.int32))
    assert int(got[3]) == 7


def collections_roundtrip():
    from collections import OrderedDict

    return OrderedDict(a=1)


def test_listeners_refuse_non_loopback_bind_without_token(monkeypatch):
    """REVIEW fix: an open, unauthenticated pickle-speaking port must never
    happen by accident — non-loopback binds are an explicit opt-in that
    requires a shared token."""
    from repro.net.ingest_server import WorkerServer
    from repro.net.query_server import QueryServer

    monkeypatch.delenv(wire.AUTH_TOKEN_ENV, raising=False)
    with pytest.raises(ValueError, match="auth token"):
        WorkerServer("0.0.0.0", 0)
    with pytest.raises(ValueError, match="auth token"):
        QueryServer(_StubEngine(), _stub_snapshot, host="0.0.0.0")
    # loopback stays the no-ceremony default
    s = WorkerServer("127.0.0.1", 0)
    s.close()
    # with a token, a routable bind is allowed
    s2 = WorkerServer("0.0.0.0", 0, auth_token="sekrit")
    s2.close()
    # the env var is an equivalent opt-in (deploys set it on both ends)
    monkeypatch.setenv(wire.AUTH_TOKEN_ENV, "sekrit")
    s3 = WorkerServer("0.0.0.0", 0)
    assert s3.auth_token == "sekrit"
    s3.close()


def test_query_server_auth_token_enforced(monkeypatch):
    """With a token configured, an unauthenticated (or wrong-token) client
    gets its connection refused and counted; the right token works."""
    from repro.net.query_server import QueryClient, QueryServer

    monkeypatch.delenv(wire.AUTH_TOKEN_ENV, raising=False)
    server = QueryServer(_StubEngine(), _stub_snapshot,
                         auth_token="sekrit").start()
    try:
        bad = QueryClient(server.address)  # never presents the token
        with pytest.raises((RuntimeError, ConnectionError, TimeoutError,
                            OSError)):
            bad.query(["a"], timeout_s=15)
        bad.close()
        wrong = QueryClient(server.address, auth_token="not-it")
        with pytest.raises((RuntimeError, ConnectionError, TimeoutError,
                            OSError)):
            wrong.query(["a"], timeout_s=15)
        wrong.close()
        good = QueryClient(server.address, auth_token="sekrit")
        assert good.query(["a"])[0] == [0.0]
        good.close()
        stats = server.stats()
        assert stats["auth_failures"] >= 1
        assert stats["served_requests"] == 1
    finally:
        server.stop()


def test_worker_server_auth_token_enforced(monkeypatch):
    """A worker host with a token aborts sessions that skip or flub auth,
    and lets an authed peer through to the hello validation."""
    from repro.net.ingest_server import WorkerServer

    monkeypatch.delenv(wire.AUTH_TOKEN_ENV, raising=False)
    server = WorkerServer("127.0.0.1", 0, auth_token="sekrit",
                          hello_timeout_s=10.0)
    host, port = server.address
    srv_thread = threading.Thread(
        target=lambda: server.serve_forever(max_sessions=2), daemon=True)
    srv_thread.start()
    try:
        # no auth: the hello is refused
        conn = socket.create_connection((host, port), timeout=10)
        wire.send_message(conn, ("hello", {"nope": True}))
        conn.close()
        _wait(lambda: server.sessions_served >= 1, timeout_s=60)
        assert "auth" in server.session_results[0]
        # authed peer reaches hello validation (junk hello, but PAST auth)
        conn2 = socket.create_connection((host, port), timeout=10)
        wire.send_message(conn2, ("auth", "sekrit"))
        wire.send_message(conn2, ("ping",))
        conn2.close()
        _wait(lambda: server.sessions_served >= 2, timeout_s=60)
        assert "expected a hello" in server.session_results[1]
    finally:
        server.stop()
        srv_thread.join(timeout=30)


# ----------------------------------------------------------- socket drain
def test_socket_backend_drain_conserves_and_matches_single_shot():
    """Tentpole gate over real TCP: a self-hosted socket worker drains the
    whole stream, epochs adopt in order into the PARENT snapshot buffer,
    conservation balances, and the counters are bit-identical to both a
    single-shot ingest (transport adds nothing, loses nothing)."""
    reg = _registry()
    t = reg.open("cit-HepPh", "kmatrix", 64, seed=0)
    epochs = []
    rt = Runtime(queue_capacity=4, publish_policy="every:2", reservoir_k=64,
                 poll_s=0.01, backend="socket")
    rt.attach(t, on_publish=lambda s: epochs.append(s.epoch))
    rt.start(pumps=False)
    assert rt.wait_ready(300)
    rt.start_pumps()
    assert rt.join_pumps(300)
    rep = rt.stop(drain=True)[t.key.tenant_id]

    assert rep["state"] == "stopped"
    assert rep["unaccounted_edges"] == 0
    assert rep["dropped_edges"] == 0
    assert rep["offered_edges"] == rep["ingested_edges"]
    assert epochs == sorted(epochs) and len(epochs) >= 1
    stream, oracle = _single_shot()
    assert rep["published_edges"] == stream.spec.n_edges
    np.testing.assert_array_equal(np.asarray(t.snapshot.sketch.pool),
                                  np.asarray(oracle.pool))
    np.testing.assert_array_equal(np.asarray(t.snapshot.sketch.conn),
                                  np.asarray(oracle.conn))


def test_remote_worker_host_drains_bit_exactly():
    """The ``stream_ingest --listen`` placement: an in-process WorkerServer
    plays the remote host, the runtime dials it via the
    ``socket:HOST:PORT`` spec, and the drain is bit-exact — the same
    contract whether the worker is a spawned child or a standing host."""
    from repro.net.ingest_server import WorkerServer

    server = WorkerServer("127.0.0.1", 0)
    host, port = server.address
    srv_thread = threading.Thread(
        target=lambda: server.serve_forever(max_sessions=1), daemon=True)
    srv_thread.start()
    try:
        reg = _registry()
        t = reg.open("cit-HepPh", "kmatrix", 64, seed=0)
        rt = Runtime(queue_capacity=4, publish_policy="every:2",
                     reservoir_k=0, poll_s=0.01,
                     backend=f"socket:{host}:{port}")
        rt.attach(t)
        rt.start(pumps=False)
        assert rt.wait_ready(300)
        rt.start_pumps()
        assert rt.join_pumps(300)
        rep = rt.stop(drain=True)[t.key.tenant_id]
        assert rep["state"] == "stopped"
        assert rep["unaccounted_edges"] == 0
        _, oracle = _single_shot()
        np.testing.assert_array_equal(np.asarray(t.snapshot.sketch.pool),
                                      np.asarray(oracle.pool))
        srv_thread.join(timeout=60)
        assert server.session_results == ["stopped"]
    finally:
        server.stop()
        server.close()


def test_worker_host_aborts_junk_session_and_stays_up():
    """A client speaking junk must kill ITS session loudly (recorded as
    aborted), not the host: a well-formed session afterwards still works."""
    from repro.net.ingest_server import WorkerServer

    server = WorkerServer("127.0.0.1", 0)
    host, port = server.address
    srv_thread = threading.Thread(
        target=lambda: server.serve_forever(max_sessions=2), daemon=True)
    srv_thread.start()
    try:
        junk = socket.create_connection((host, port), timeout=10)
        junk.sendall(b"GET / HTTP/1.1\r\n\r\n")
        junk.close()
        _wait(lambda: server.sessions_served >= 1, timeout_s=60)
        assert server.session_results[0].startswith("aborted")

        reg = _registry()
        t = reg.open("cit-HepPh", "kmatrix", 64, seed=3)
        rt = Runtime(queue_capacity=4, publish_policy="drain:0",
                     reservoir_k=0, poll_s=0.01,
                     backend=f"socket:{host}:{port}")
        rt.attach(t, max_batches=2)
        rt.start()
        assert rt.join_pumps(300)
        rep = rt.stop(drain=True)[t.key.tenant_id]
        assert rep["state"] == "stopped"
        assert rep["unaccounted_edges"] == 0
    finally:
        server.stop()
        server.close()
        srv_thread.join(timeout=30)


# ------------------------------------------------ dead peer + crash-resume
def test_dead_tcp_peer_fails_worker_with_accounting():
    """Satellite: killing the remote end mid-stream must surface as a
    FAILED worker whose error carries last-known accounting, and
    ``Runtime.stop()`` must raise ``WorkerFailure`` with the report —
    never a silent hang (mirror of the process backend's SIGKILL path)."""
    reg = _registry()
    t = reg.open("cit-HepPh", "kmatrix", 64, seed=4)
    rt = Runtime(queue_capacity=2, publish_policy="every:2", reservoir_k=0,
                 poll_s=0.01, backend="socket")
    h = rt.attach(t, throttle_s=0.05)
    rt.start(pumps=False)
    assert rt.wait_ready(300)
    rt.start_pumps()
    _wait(lambda: h.worker.metrics_snapshot()["ingested_batches"] >= 2,
          timeout_s=300)
    os.kill(h.worker.process.pid, signal.SIGKILL)
    _wait(lambda: h.worker.state == "failed", timeout_s=60)
    assert "lost its TCP peer" in repr(h.worker.error)
    assert "last-known accounting" in repr(h.worker.error)
    assert "ingested_edges=" in repr(h.worker.error)
    with pytest.raises(WorkerFailure, match="lost its TCP peer") as excinfo:
        rt.stop(drain=True)
    assert excinfo.value.report[t.key.tenant_id]["state"] == "failed"


def test_standing_host_connection_blip_redials_quietly():
    """ISSUE 8 satellite: a dropped connection to a STANDING worker host
    gets ONE quiet re-dial — the parent replays retained unadopted items
    into a fresh session (whose first publish is a full resync by
    construction) — and the drain stays conserving and bit-exact with no
    WorkerFailure.  Self-hosted peers keep the loud fail-fast path (see
    ``test_dead_tcp_peer_fails_worker_with_accounting``)."""
    from repro.net.ingest_server import WorkerServer

    server = WorkerServer("127.0.0.1", 0)
    host, port = server.address
    srv_thread = threading.Thread(
        target=lambda: server.serve_forever(max_sessions=2), daemon=True)
    srv_thread.start()
    try:
        reg = _registry()
        t = reg.open("cit-HepPh", "kmatrix", 64, seed=0)
        rt = Runtime(queue_capacity=4, publish_policy="every:2",
                     reservoir_k=0, poll_s=0.01,
                     backend=f"socket:{host}:{port}")
        h = rt.attach(t, throttle_s=0.05)
        rt.start(pumps=False)
        assert rt.wait_ready(300)
        rt.start_pumps()
        # mid-stream, with adopted publishes behind us, sever the link
        _wait(lambda: h.worker.metrics_snapshot()["ingested_batches"] >= 2,
              timeout_s=300)
        h.worker._sock.shutdown(socket.SHUT_RDWR)
        assert rt.join_pumps(300)
        rep = rt.stop(drain=True)[t.key.tenant_id]
        assert rep["state"] == "stopped"
        assert rep["unaccounted_edges"] == 0
        assert rep["dropped_edges"] == 0
        assert h.worker._redial_used, "the blip must have used the re-dial"
        stream, oracle = _single_shot()
        assert rep["published_edges"] == stream.spec.n_edges
        np.testing.assert_array_equal(np.asarray(t.snapshot.sketch.pool),
                                      np.asarray(oracle.pool))
        np.testing.assert_array_equal(np.asarray(t.snapshot.sketch.conn),
                                      np.asarray(oracle.conn))
        srv_thread.join(timeout=60)
        assert server.sessions_served == 2, server.session_results
        # first session died with the link (worker-side "failed" or a
        # transport abort, depending on who noticed first); the re-dialed
        # session is the one that must finish cleanly
        assert server.session_results[0] != "stopped"
        assert server.session_results[1] == "stopped"
    finally:
        server.stop()
        server.close()


def test_socket_sharded_sigkill_resume_conserves_and_serves_exactly(
        tmp_path):
    """Satellite acceptance over TCP (mirror of the process-backend crash
    test): SIGKILL one shard's self-hosted socket worker mid-stream, tear
    the rest down crash-like, restore every shard from its checkpoint via
    the manifest (which must record the socket backend), drain — per-shard
    conservation holds, the merged state is bit-identical to a
    never-crashed single sketch, and engine == direct on the restore."""
    ckpt = str(tmp_path / "ckpt")
    reg_a = _registry()
    st_a = reg_a.open_sharded("cit-HepPh", "kmatrix", 64, seed=0, n_shards=2)
    rt_a = Runtime(queue_capacity=2, publish_policy="every:2", reservoir_k=0,
                   checkpoint_dir=ckpt, checkpoint_every=1, poll_s=0.01,
                   backend="socket")
    handles_a = attach_shards(rt_a, st_a, throttle_s=[0.05, 0.12])
    rt_a.start(pumps=False)
    assert rt_a.wait_ready(300)
    rt_a.start_pumps()
    _wait(lambda: all(h.worker.metrics_snapshot()["checkpoints"] >= 1
                      for h in handles_a), timeout_s=300)
    _wait(lambda: handles_a[0].worker.metrics_snapshot()["ingested_batches"]
          >= 3, timeout_s=300)
    victim = handles_a[0].worker
    os.kill(victim.process.pid, signal.SIGKILL)
    _wait(lambda: victim.state == "failed", timeout_s=60)
    assert "lost its TCP peer" in repr(victim.error)
    rt_a.kill()
    nb = st_a.stream.num_batches
    manifest = read_shard_manifest(ckpt)
    assert manifest["n_shards"] == 2
    assert manifest["runtime_backend"] == "socket"

    reg_b = _registry()
    st_b = reg_b.open_sharded("cit-HepPh", "kmatrix", 64, seed=0,
                              n_shards=manifest["n_shards"],
                              shard_seed=manifest["shard_seed"])
    rt_b = Runtime(queue_capacity=4, publish_policy="every:2", reservoir_k=0,
                   checkpoint_dir=ckpt, poll_s=0.01, backend="socket")
    handles_b = attach_shards(rt_b, st_b, restore=True)
    restored_offsets = [s.offset for s in st_b.shards]
    assert any(0 < o for o in restored_offsets), \
        "restore must resume from the checkpoints, not from scratch"
    assert any(o < nb for o in restored_offsets), "kill was not mid-stream"
    rt_b.start(pumps=False)
    assert rt_b.wait_ready(300)
    rt_b.start_pumps()
    assert rt_b.join_pumps(300)
    rt_b.stop(drain=True)

    cons = sharded_conservation(handles_b, st_b.stream.spec.n_edges)
    assert all(u == 0 for u in cons["per_shard_unaccounted"]), cons

    stream, oracle = _single_shot()
    merged = st_b.merged_snapshot()
    np.testing.assert_array_equal(np.asarray(merged.sketch.pool),
                                  np.asarray(oracle.pool))
    np.testing.assert_array_equal(np.asarray(merged.sketch.conn),
                                  np.asarray(oracle.conn))
    assert merged.n_edges == stream.spec.n_edges

    engine = ShardedQueryEngine(QueryEngine(min_bucket=8))
    snap = st_b.snapshot
    reqs = synth_requests(32, mix_for_sketch("kmatrix"),
                          n_nodes=stream.spec.n_nodes, seed=11,
                          heavy_universe=256, heavy_threshold=5.0)
    got = [r.value for r in engine.execute(snap, reqs)]
    want = sharded_direct_answers(snap, reqs)
    for g, w in zip(got, want):
        assert values_match(g, w)


# ------------------------------------------------------- query front-end
class _StubEngine:
    """Duck-typed engine: QueryServer only needs execute()."""

    def __init__(self, delay_s=0.0, fail=False):
        self.delay_s = delay_s
        self.fail = fail
        self.calls = 0

    def execute(self, snapshot, requests):
        self.calls += 1
        if self.fail:
            raise RuntimeError("engine-kaboom")
        if self.delay_s:
            time.sleep(self.delay_s)
        return [types.SimpleNamespace(epoch=snapshot.epoch, value=float(i))
                for i, _ in enumerate(requests)]


def _stub_snapshot(epoch=5, n_edges=1234):
    return types.SimpleNamespace(epoch=epoch, n_edges=n_edges)


def test_query_server_roundtrip_epoch_stamped():
    from repro.net.query_server import QueryClient, QueryServer

    snap = _stub_snapshot(epoch=7)
    server = QueryServer(_StubEngine(), lambda: snap,
                         info={"kind": "stub"}).start()
    try:
        client = QueryClient(server.address)
        info = client.info()
        assert info["kind"] == "stub" and info["epoch"] == 7
        values, epoch = client.query(["a", "b", "c"])
        assert values == [0.0, 1.0, 2.0]
        assert epoch == 7  # every answer names the epoch it came from
        snap.epoch = 9  # snapshot_fn is re-polled per batch: fresh epochs
        _, epoch = client.query(["a"])
        assert epoch == 9
        client.close()
        # replies are sent before the ledger update; wait out the race
        _wait(lambda: server.stats()["served_requests"] == 4, timeout_s=30)
        stats = server.stats()
        assert stats["offered_requests"] == stats["admitted_requests"] == 4
    finally:
        server.stop()


def test_query_server_admission_shed_is_accounted():
    """Satellite: overload shed is never silent.  With a slow engine and a
    tiny inflight budget, concurrent clients MUST see rejections carrying a
    positive Retry-After hint, and the server ledger must balance exactly:
    offered == admitted + shed, admitted == served."""
    from repro.net.query_server import QueryClient, QueryServer

    server = QueryServer(_StubEngine(delay_s=0.05), _stub_snapshot,
                         max_inflight=2, batch_max=2).start()
    outcomes = []
    lock = threading.Lock()

    def hammer():
        client = QueryClient(server.address)
        for _ in range(5):
            payload = client.call(["q", "r"])
            with lock:
                outcomes.append(payload)
        client.close()

    try:
        threads = [threading.Thread(target=hammer) for _ in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        _wait(lambda: server.stats()["inflight"] == 0, timeout_s=30)
        stats = server.stats()
    finally:
        server.stop()
    kinds = [p["kind"] for p in outcomes]
    assert kinds.count("result") + kinds.count("reject") == len(outcomes)
    assert kinds.count("reject") > 0, "6x2 concurrent vs max_inflight=2 " \
        "never shed — admission control is not engaging"
    for p in outcomes:
        if p["kind"] == "reject":
            assert p["reason"] == "overloaded"
            assert p["retry_after_ms"] > 0
    assert stats["offered_requests"] == (stats["admitted_requests"]
                                         + stats["shed_overload"]
                                         + stats["shed_rate_limited"]
                                         + stats["shed_too_large"])
    assert stats["offered_requests"] == 2 * len(outcomes)
    assert stats["served_requests"] == stats["admitted_requests"]
    assert 2 * kinds.count("result") == stats["served_requests"]


def test_query_server_per_tenant_rate_limit():
    from repro.net.query_server import QueryClient, QueryServer, Rejected

    server = QueryServer(_StubEngine(), _stub_snapshot,
                         tenant_qps=1.0, tenant_burst=2.0).start()
    try:
        noisy = QueryClient(server.address, tenant="noisy")
        noisy.query(["a", "b"])  # burst allows this
        with pytest.raises(Rejected) as excinfo:
            noisy.query(["c"])  # bucket empty: ~1s to refill
        assert excinfo.value.reason == "rate_limited"
        assert excinfo.value.retry_after_ms > 0
        # another tenant has its own bucket — not collateral damage
        quiet = QueryClient(server.address, tenant="quiet")
        assert quiet.query(["x"])[0] == [0.0]
        noisy.close()
        quiet.close()
        assert server.stats()["shed_rate_limited"] == 1
    finally:
        server.stop()


def test_query_server_rejects_never_admittable_frames_as_too_large():
    """REVIEW fix: a frame bigger than the smallest admission ceiling can
    never succeed, so it must be rejected with a distinct ``too_large``
    verdict (naming the limit, no lying retry-after) — and counted."""
    from repro.net.query_server import QueryClient, QueryServer, Rejected

    # bigger than tenant_burst: the token bucket caps at burst forever
    server = QueryServer(_StubEngine(), _stub_snapshot,
                         tenant_qps=5.0, tenant_burst=2.0).start()
    try:
        client = QueryClient(server.address)
        with pytest.raises(Rejected) as excinfo:
            client.query(["a", "b", "c"])
        assert excinfo.value.reason == "too_large"
        assert client.query(["a", "b"])[0] == [0.0, 1.0]  # burst-sized: fine
        client.close()
        stats = server.stats()
        assert stats["shed_too_large"] == 3
        assert stats["offered_requests"] == (stats["admitted_requests"]
                                             + stats["shed_overload"]
                                             + stats["shed_rate_limited"]
                                             + stats["shed_too_large"])
    finally:
        server.stop()
    # bigger than max_inflight: inflight + n > cap for every inflight >= 0
    server = QueryServer(_StubEngine(), _stub_snapshot,
                         max_inflight=2).start()
    try:
        client = QueryClient(server.address)
        payload = client.call(["a", "b", "c"])
        assert payload["kind"] == "reject"
        assert payload["reason"] == "too_large"
        assert payload["max_requests"] == 2
        client.close()
    finally:
        server.stop()


def test_slow_reader_stalls_only_its_own_connection():
    """REVIEW fix (head-of-line blocking): replies go through bounded
    per-connection writer queues, so a client that never reads its socket
    cannot stall the shared executor — concurrent well-behaved clients
    keep getting answers immediately while the stalled connection alone
    overflows and is dropped."""
    from repro.net.query_server import QueryClient, QueryServer

    class BigEngine:
        def execute(self, snapshot, requests):
            # ~1 MB per reply so a handful overfills any socket buffer
            return [types.SimpleNamespace(epoch=snapshot.epoch,
                                          value="x" * (1 << 20))
                    for _ in requests]

    server = QueryServer(BigEngine(), _stub_snapshot,
                         frame_deadline_s=2.0, reply_queue_max=4).start()
    try:
        stall = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        stall.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, 4096)
        stall.connect(server.address)
        for i in range(16):  # ~16 MB of replies nobody will ever read
            wire.send_message(stall, ("query", {"id": i, "tenant": "stall",
                                                "requests": ["q"]}))
        fast = QueryClient(server.address, frame_deadline_s=30.0)
        t0 = time.monotonic()
        values, _ = fast.query(["a"], timeout_s=30)
        fast_latency = time.monotonic() - t0
        assert values == ["x" * (1 << 20)]
        # without per-connection writers the executor would be wedged in
        # 2 s-deadline sends to the stalled socket and this would take
        # many seconds; with them it's immediate
        assert fast_latency < 2.0, \
            f"well-behaved client waited {fast_latency:.1f}s behind a " \
            "stalled one — executor is blocking on slow-client sends"
        fast.close()
        stall.close()
        # nothing silently lost: everything offered is accounted admitted
        # (the stalled client's replies were executed then dropped at ITS
        # dead connection, which is a delivery failure, not a shed)
        stats = server.stats()
        assert stats["offered_requests"] == (stats["admitted_requests"]
                                             + stats["shed_overload"]
                                             + stats["shed_rate_limited"]
                                             + stats["shed_too_large"])
    finally:
        server.stop()


def test_netloadgen_counts_transport_death_as_aborted_not_shed():
    """REVIEW fix: a connection that dies mid-run (reset/timeout) must
    surface as ``aborted`` + ``transport_error`` in the report, never be
    folded into ``shed`` — sheds are the server's accounted admission
    decisions, not client-side casualties."""
    from repro.serving.loadgen import NetLoadGen

    listener = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    listener.bind(("127.0.0.1", 0))
    listener.listen(1)
    address = listener.getsockname()[:2]

    def half_server():
        conn, _ = listener.accept()
        try:
            msg = wire.recv_message(conn, poll_s=30.0)
            wire.send_message(conn, ("result", {
                "id": msg[1]["id"], "epoch": 1,
                "values": [0.0] * len(msg[1]["requests"])}))
        finally:
            conn.close()  # dies after one answer

    srv = threading.Thread(target=half_server, daemon=True)
    srv.start()
    try:
        gen = NetLoadGen(target_qps=100000.0, connections=1, batch_max=4)
        rep = gen.run(address, list(range(40)))
    finally:
        srv.join(timeout=30)
        listener.close()
    assert rep.accepted == 4  # the one answered batch
    assert rep.aborted == 36  # in-flight + unsent remainder
    assert rep.shed == 0, "transport death was misaccounted as shed"
    assert rep.errors == 0
    assert rep.transport_error is not None
    assert (rep.accepted + rep.shed + rep.errors + rep.aborted
            == rep.n_requests)


def test_query_server_engine_error_answered_not_fatal():
    """An engine exception answers THAT call as an error and the server
    keeps serving; junk frames kill only their own session."""
    from repro.net.query_server import QueryClient, QueryServer

    engine = _StubEngine(fail=True)
    snap = _stub_snapshot()
    server = QueryServer(engine, lambda: snap).start()
    try:
        client = QueryClient(server.address)
        with pytest.raises(RuntimeError, match="engine-kaboom"):
            client.query(["a"])
        engine.fail = False
        assert client.query(["a"])[0] == [0.0]  # same connection, recovered
        client.close()
        # a junk-speaking client: its session dies, the server does not
        junk = socket.create_connection(server.address, timeout=10)
        junk.sendall(b"\x00" * 64)
        junk.close()
        c2 = QueryClient(server.address)
        assert c2.query(["a"])[0] == [0.0]
        c2.close()
        _wait(lambda: server.stats()["served_requests"] == 2, timeout_s=30)
        assert server.stats()["errored_requests"] == 1
    finally:
        server.stop()


@pytest.mark.slow
def test_multi_connection_soak_live_ingest():
    """Soak (slow lane): 8 loadgen connections against the TCP front-end
    over a LIVE-ingesting tenant for thousands of requests — zero errors,
    every request accounted, answers epoch-stamped and the freshest answer
    at least as new as the first publish."""
    from repro.net.query_server import QueryServer
    from repro.serving import warm_bucket_ladder
    from repro.serving.loadgen import NetLoadGen

    reg = _registry()
    t = reg.open("cit-HepPh", "kmatrix", 64, seed=0)
    t.step(2)
    t.publish()
    n_nodes = t.stream.spec.n_nodes
    engine = QueryEngine(min_bucket=8)
    mix = mix_for_sketch("kmatrix")
    kw = dict(n_nodes=n_nodes, heavy_universe=256, heavy_threshold=5.0)
    warm_bucket_ladder(engine, t.snapshot, synth_requests(64, mix, seed=99,
                                                          **kw))
    stop_ingest = threading.Event()

    def live_ingest():
        while not stop_ingest.is_set():
            if not t.step(1):
                break
            t.publish()
            time.sleep(0.02)

    ingester = threading.Thread(target=live_ingest, daemon=True)
    server = QueryServer(engine, lambda: t.snapshot).start()
    first_epoch = t.snapshot.epoch
    ingester.start()
    try:
        reqs = synth_requests(4000, mix, seed=13, **kw)
        rep = NetLoadGen(target_qps=400.0, connections=8,
                         batch_max=64).run(server.address, reqs)
    finally:
        stop_ingest.set()
        ingester.join(timeout=60)
        server.stop()
    assert rep.errors == 0
    assert rep.accepted + rep.shed == rep.n_requests
    assert rep.accepted == rep.n_requests  # nominal load: nothing shed
    assert rep.last_epoch is not None and rep.last_epoch >= first_epoch
    assert np.isfinite(rep.p99_ms)
    stats = server.stats()
    assert stats["offered_requests"] == (stats["admitted_requests"]
                                         + stats["shed_overload"]
                                         + stats["shed_rate_limited"]
                                         + stats["shed_too_large"])
