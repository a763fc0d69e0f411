"""Process execution backend (ISSUE 5 tentpole): spawn children owning
their sketches under the transport-agnostic runtime contract — drain
conservation + bit-exactness vs a single-shot ingest, SIGKILL crash-resume
through per-shard checkpoints + the shard manifest, worker-failure
propagation to ``Runtime.stop()``, manifest corruption hard-failing
restore, and the graceful signal-drain path (DESIGN.md §Runtime
§Backends)."""
import json
import os
import signal
import time

import numpy as np
import jax
import pytest

from repro.core import kmatrix
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


# ----------------------------------------------------------- process drain
def test_process_backend_drain_conserves_and_matches_single_shot():
    """The tentpole gate on the process backend: a pump-fed spawn child
    drains the whole stream, every published epoch lands in the PARENT's
    snapshot buffer, conservation balances, and the final counters are
    bit-identical to a single-shot ingest."""
    reg = _registry()
    t = reg.open("cit-HepPh", "kmatrix", 64, seed=0)
    epochs = []
    rt = Runtime(queue_capacity=4, publish_policy="every:2", reservoir_k=64,
                 poll_s=0.01, backend="process")
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


def test_process_backend_requires_registry_tenant_and_policy_spec():
    reg = _registry()
    t = reg.open("cit-HepPh", "kmatrix", 64, seed=1)
    t_bare = reg.open("cit-HepPh", "kmatrix", 64, seed=2)
    t_bare.origin = None  # simulate a hand-built tenant
    rt = Runtime(backend="process", reservoir_k=0)
    with pytest.raises(ValueError, match="registry-opened"):
        rt.attach(t_bare)
    from repro.runtime import EveryNBatches
    rt2 = Runtime(backend="process", reservoir_k=0,
                  publish_policy=EveryNBatches(2))
    with pytest.raises(TypeError, match="SPEC string"):
        rt2.attach(t)
    with pytest.raises(ValueError, match="runtime backend"):
        Runtime(backend="fiber")


# ------------------------------------------------- SIGKILL crash + resume
def test_process_sharded_sigkill_resume_conserves_and_serves_exactly(
        tmp_path):
    """Satellite acceptance (mirror of the thread crash test in
    test_sharding.py): SIGKILL one shard's worker PROCESS mid-stream,
    tear the rest down crash-like, restore every shard from its last
    checkpoint via the manifest, drain — per-shard conservation holds and
    the merged state is bit-identical to a never-crashed single sketch,
    with engine == direct on the restored registry."""
    ckpt = str(tmp_path / "ckpt")
    reg_a = _registry()
    st_a = reg_a.open_sharded("cit-HepPh", "kmatrix", 64, seed=0, n_shards=2)
    rt_a = Runtime(queue_capacity=2, publish_policy="every:2", reservoir_k=0,
                   checkpoint_dir=ckpt, checkpoint_every=1, poll_s=0.01,
                   backend="process")
    # different throttles drive the shards to different stream offsets
    handles_a = attach_shards(rt_a, st_a, throttle_s=[0.05, 0.12])
    rt_a.start(pumps=False)
    assert rt_a.wait_ready(300)
    rt_a.start_pumps()
    _wait(lambda: all(h.worker.metrics_snapshot()["checkpoints"] >= 1
                      for h in handles_a))
    _wait(lambda: handles_a[0].worker.metrics_snapshot()["ingested_batches"]
          >= 3)
    victim = handles_a[0].worker
    os.kill(victim.process.pid, signal.SIGKILL)
    _wait(lambda: victim.state == "failed", timeout_s=60)
    assert "exitcode" in repr(victim.error)
    rt_a.kill()
    # the kill must be mid-stream for at least one shard
    nb = st_a.stream.num_batches
    manifest = read_shard_manifest(ckpt)
    assert manifest["n_shards"] == 2
    assert manifest["runtime_backend"] == "process"

    reg_b = _registry()
    st_b = reg_b.open_sharded("cit-HepPh", "kmatrix", 64, seed=0,
                              n_shards=manifest["n_shards"],
                              shard_seed=manifest["shard_seed"])
    rt_b = Runtime(queue_capacity=4, publish_policy="every:2", reservoir_k=0,
                   checkpoint_dir=ckpt, poll_s=0.01, backend="process")
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


def test_parent_side_publish_failure_terminates_child():
    """A parent-side adoption failure (e.g. an on_publish callback raising)
    must not leak a live child: the handle goes failed AND the child is
    terminated, and the failure surfaces at stop()."""
    reg = _registry()
    t = reg.open("cit-HepPh", "kmatrix", 64, seed=7)
    rt = Runtime(queue_capacity=4, publish_policy="every:1", reservoir_k=0,
                 poll_s=0.01, backend="process")

    def bad_callback(snap):
        raise RuntimeError("callback-kaboom")

    h = rt.attach(t, on_publish=bad_callback)
    rt.start()
    _wait(lambda: h.worker.state == "failed", timeout_s=180)
    assert "callback-kaboom" in (h.worker.error_tb or "")
    _wait(lambda: not h.worker.process.is_alive(), timeout_s=30)
    with pytest.raises(WorkerFailure, match="callback-kaboom"):
        rt.stop(drain=True)


# ------------------------------------------------- failure propagation
def test_worker_failure_propagates_to_stop_with_traceback():
    """Satellite: a failed worker must surface at the Runtime.stop() call
    site — original exception AND traceback — not only via health()."""
    reg = _registry()
    t = reg.open("cit-HepPh", "kmatrix", 64, seed=5)
    rt = Runtime(queue_capacity=4, publish_policy="every:2", reservoir_k=0,
                 poll_s=0.01)
    handle = rt.attach(t, max_batches=3)

    def explode(item, now):
        raise RuntimeError("boom-at-ingest")

    handle.worker._ingest = explode
    rt.start()
    _wait(lambda: not handle.worker.is_alive())
    with pytest.raises(WorkerFailure) as excinfo:
        rt.stop(drain=True)
    err = excinfo.value
    assert "boom-at-ingest" in str(err)
    assert err.failures[0]["tenant_id"] == t.key.tenant_id
    assert "boom-at-ingest" in (err.failures[0]["traceback"] or "")
    # the accounting report still rides along for the caller
    assert err.report[t.key.tenant_id]["state"] == "failed"
    # and an explicit opt-out returns the report instead of raising
    rep = rt.stop(drain=True, raise_on_failure=False)
    assert rep[t.key.tenant_id]["state"] == "failed"


def test_graceful_signal_drain_flushes_checkpoint(tmp_path):
    """Satellite: SIGTERM on a serving driver drains and flushes a final
    checkpoint before exiting 128+signum (install_graceful_drain)."""
    from repro.checkpoint import store
    from repro.launch.query_serve import install_graceful_drain

    old_term = signal.getsignal(signal.SIGTERM)
    old_int = signal.getsignal(signal.SIGINT)
    try:
        ckpt = str(tmp_path / "ckpt")
        reg = _registry()
        t = reg.open("cit-HepPh", "kmatrix", 64, seed=6)
        rt = Runtime(queue_capacity=4, publish_policy="every:100000",
                     reservoir_k=0, checkpoint_dir=ckpt, poll_s=0.01)
        handle = rt.attach(t, throttle_s=0.01)
        install_graceful_drain(rt)
        rt.start()
        _wait(lambda: handle.worker.metrics.ingested_batches >= 2)
        with pytest.raises(SystemExit) as excinfo:
            os.kill(os.getpid(), signal.SIGTERM)
            # the handler runs on the main thread at the next bytecode
            # boundary; give it one
            time.sleep(5)
        assert excinfo.value.code == 128 + signal.SIGTERM
        # the drain conserved every offered edge (the pump stops early on
        # shutdown — full-stream ingest is NOT the contract here) and the
        # final checkpoint made it to disk for the next --restore
        cons = handle.conservation()
        assert cons["unaccounted_edges"] == 0
        assert t.snapshot.n_edges > 0
        tenant_dir = rt._tenant_dir(ckpt, t)
        assert store.latest_step(tenant_dir) is not None
        meta = store.read_meta(tenant_dir)
        assert meta["extra"]["n_edges"] == t.snapshot.n_edges
    finally:
        signal.signal(signal.SIGTERM, old_term)
        signal.signal(signal.SIGINT, old_int)


# ------------------------------------------------- manifest hardening
def test_truncated_shard_manifest_fails_restore_loudly(tmp_path):
    ckpt = str(tmp_path / "ckpt")
    reg = _registry()
    st = reg.open_sharded("cit-HepPh", "kmatrix", 64, seed=0, n_shards=2)
    rt = Runtime(queue_capacity=4, publish_policy="every:2", reservoir_k=0,
                 checkpoint_dir=ckpt, checkpoint_every=1, poll_s=0.01)
    attach_shards(rt, st, max_batches=1)
    rt.start()
    rt.join_pumps(120)
    rt.stop(drain=True)
    manifest_path = os.path.join(ckpt, "shard_manifest.json")
    full = open(manifest_path).read()
    assert json.loads(full)["runtime_backend"] == "thread"

    # torn write: keep only the first half of the JSON
    with open(manifest_path, "w") as f:
        f.write(full[: len(full) // 2])
    with pytest.raises(ValueError, match="truncated or corrupt"):
        read_shard_manifest(ckpt)
    other = _registry().open_sharded("cit-HepPh", "kmatrix", 64, seed=0,
                                     n_shards=2)
    rt2 = Runtime(queue_capacity=4, reservoir_k=0, checkpoint_dir=ckpt,
                  poll_s=0.01)
    with pytest.raises(ValueError, match="truncated or corrupt"):
        attach_shards(rt2, other, restore=True)

    # a manifest missing required keys is just as unverifiable
    with open(manifest_path, "w") as f:
        json.dump({"n_shards": 2}, f)
    with pytest.raises(ValueError, match="missing required keys"):
        read_shard_manifest(ckpt)


def test_spawning_backends_refuse_beside_an_accelerator(monkeypatch):
    """One process per chip: when this process's JAX backend is a TPU, the
    process and self-hosted socket backends refuse to spawn JAX children
    unless ``child_env`` places them, and say where the sketches live."""
    from repro.net.backend import SocketBackend
    from repro.runtime.backend import ProcessBackend

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="use the thread backend"):
        ProcessBackend()
    with pytest.raises(RuntimeError, match="use the thread backend"):
        Runtime(backend="process")
    with pytest.raises(RuntimeError, match="use the thread backend"):
        SocketBackend()
    assert (ProcessBackend(child_env={"JAX_PLATFORMS": "cpu"}).placement
            == "child processes (JAX_PLATFORMS=cpu)")
    # dialed worker hosts were placed by whoever started them
    assert (SocketBackend(addresses=[("127.0.0.1", 7733)]).placement
            == "worker hosts 127.0.0.1:7733")
    assert Runtime(backend="thread").backend.placement == "in-process"
