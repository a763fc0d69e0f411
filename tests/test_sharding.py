"""Sharded serving seams (ISSUE 4 tentpole): hash-band routing invariants,
merged-vs-unsharded bit-exactness, scatter/gather engine == direct oracle,
per-shard runtime conservation, and sharded crash-resume through the shard
manifest (DESIGN.md §Sharding)."""
import time

import numpy as np
import jax
import pytest

from repro.core import ShardPlan, kmatrix
from repro.core.partitioning import ShardPlan as ShardPlanDirect
from repro.runtime import Runtime
from repro.serving import (
    QueryEngine,
    ShardStreamView,
    ShardedQueryEngine,
    SketchRegistry,
    attach_shards,
    mix_for_sketch,
    read_shard_manifest,
    sharded_conservation,
    sharded_direct_answers,
    synth_requests,
)
from repro.serving import engine as eng


def _registry(**kw):
    kw.setdefault("depth", 3)
    kw.setdefault("batch_size", 1024)
    kw.setdefault("scale", 0.02)
    return SketchRegistry(**kw)


def _single_shot(registry_kwargs=None, dataset="cit-HepPh", kind="kmatrix",
                 budget_kb=64, seed=0):
    """Oracle: the whole stream ingested once into one sketch, no sharding."""
    reg = _registry(**(registry_kwargs or {}))
    t = reg.open(dataset, kind, budget_kb, seed=seed)
    sk = t.snapshot.sketch
    ing = jax.jit(kmatrix.ingest)
    for b in t.stream:
        sk = ing(sk, b)
    return t.stream, sk


def _values_match(a, b) -> bool:
    if isinstance(a, tuple):
        return np.array_equal(a[0], b[0]) and np.array_equal(a[1], b[1])
    return a == b


def _wait(cond, timeout_s=60.0, poll_s=0.005):
    deadline = time.monotonic() + timeout_s
    while not cond():
        if time.monotonic() >= deadline:
            raise TimeoutError("condition not met in time")
        time.sleep(poll_s)


# ----------------------------------------------------------------- routing
def test_shard_plan_is_deterministic_and_total():
    plan = ShardPlan(4, seed=3)
    v = np.arange(50_000, dtype=np.int64)
    a = plan.shard_of(v)
    b = ShardPlanDirect(4, seed=3).shard_of(v)  # same export, fresh instance
    np.testing.assert_array_equal(a, b)
    assert a.min() >= 0 and a.max() < 4
    counts = np.bincount(a, minlength=4)
    assert counts.min() > 0, "a band received nothing on 50k vertices"
    # scalar path agrees with the vectorized path
    assert plan.shard_of_one(12345) == int(a[12345])
    # a different routing seed produces a different banding
    assert not np.array_equal(a, ShardPlan(4, seed=4).shard_of(v))
    with pytest.raises(ValueError, match="n_shards"):
        ShardPlan(0)


def test_shard_views_partition_the_stream():
    """Every non-padding edge of every batch lands in exactly one view."""
    reg = _registry()
    t = reg.open("cit-HepPh", "kmatrix", 64, seed=0)
    plan = ShardPlan(3, seed=0)
    views = [ShardStreamView(t.stream, plan, s) for s in range(3)]
    total = 0
    for i in range(t.stream.num_batches):
        _, _, w = t.stream.batch_numpy(i)
        base_edges = int((w > 0).sum())
        shard_edges = 0
        for view in views:
            _, _, vw = view.batch_numpy(i)
            shard_edges += int((vw > 0).sum())
        assert shard_edges == base_edges, f"batch {i} lost/duplicated edges"
        total += base_edges
    assert total == t.stream.spec.n_edges


def test_shard_view_batches_are_replayable_and_bucketed():
    reg = _registry()
    t = reg.open("cit-HepPh", "kmatrix", 64, seed=1)
    view = ShardStreamView(t.stream, ShardPlan(2, seed=0), 0)
    s1, d1, w1 = view.batch_numpy(0)
    s2, d2, w2 = view.batch_numpy(0)  # pure fn of (base, plan, shard, i)
    np.testing.assert_array_equal(s1, s2)
    np.testing.assert_array_equal(w1, w2)
    assert len(s1) >= view.min_bucket and len(s1) % view.granule == 0
    own = w1 > 0
    assert np.all(view.plan.shard_of(s1[own]) == 0)


# ------------------------------------------------- merged == single sketch
def test_sharded_merge_equals_single_sketch_replay():
    """Tentpole gate: after a full cooperative ingest, the merge of the K
    shard sketches is bit-identical to one sketch that saw the whole
    stream, and so are its estimates."""
    reg = _registry()
    st = reg.open_sharded("cit-HepPh", "kmatrix", 64, seed=0, n_shards=3)
    st.step(st.stream.num_batches)
    snap = st.publish()
    assert snap.n_edges == st.stream.spec.n_edges
    merged = st.merged_snapshot()

    stream, oracle = _single_shot()
    np.testing.assert_array_equal(np.asarray(merged.sketch.pool),
                                  np.asarray(oracle.pool))
    np.testing.assert_array_equal(np.asarray(merged.sketch.conn),
                                  np.asarray(oracle.conn))


def test_open_sharded_is_idempotent_and_shards_share_layout():
    reg = _registry()
    a = reg.open_sharded("cit-HepPh", "kmatrix", 64, seed=0, n_shards=2)
    assert reg.open_sharded("cit-HepPh", "kmatrix", 64, seed=0,
                            n_shards=2) is a
    sk0 = a.shards[0].snapshot.sketch
    sk1 = a.shards[1].snapshot.sketch
    # same hash family and routing -> merge is legal and meaningful
    np.testing.assert_array_equal(np.asarray(sk0.hashes.a),
                                  np.asarray(sk1.hashes.a))
    np.testing.assert_array_equal(np.asarray(sk0.route.offsets),
                                  np.asarray(sk1.route.offsets))
    ids = [s.key.tenant_id for s in a.shards]
    assert len(set(ids)) == 2 and all("shard" in i for i in ids)


# --------------------------------------------------------- engine == oracle
def test_sharded_engine_matches_sharded_direct_oracle():
    reg = _registry()
    st = reg.open_sharded("cit-HepPh", "kmatrix", 64, seed=0, n_shards=3)
    st.step(4)
    snap = st.publish()
    engine = ShardedQueryEngine(QueryEngine(min_bucket=8))
    reqs = synth_requests(64, mix_for_sketch("kmatrix"),
                          n_nodes=st.stream.spec.n_nodes, seed=5,
                          heavy_universe=512, heavy_threshold=5.0)
    got = [r.value for r in engine.execute(snap, reqs)]
    want = sharded_direct_answers(snap, reqs)
    for i, (g, w) in enumerate(zip(got, want)):
        assert _values_match(g, w), (i, reqs[i].family, g, w)
    # every result in a batch carries ONE epoch-vector stamp
    stamps = {r.epoch for r in engine.execute(snap, reqs[:8])}
    assert stamps == {snap.epochs}


def test_sharded_reach_closure_cache_keys_on_epoch_vector():
    reg = _registry()
    st = reg.open_sharded("cit-HepPh", "kmatrix", 64, seed=0, n_shards=2)
    st.step(2)
    snap = st.publish()
    engine = ShardedQueryEngine(QueryEngine(min_bucket=8))
    reqs = [eng.reach(1, 9), eng.reach(4, 2)]
    engine.execute(snap, reqs)
    assert engine.closures.misses == 1
    engine.execute(snap, reqs)
    assert engine.closures.hits >= 1
    # ONE shard publishing invalidates (new epoch vector -> new key)
    st.shards[0].step(1)
    st.shards[0].publish()
    misses_before = engine.closures.misses
    engine.execute(st.snapshot, reqs)
    assert engine.closures.misses == misses_before + 1


# ------------------------------------------------------- runtime + restore
@pytest.mark.parametrize("n_shards", [1, 2, 3, 4])
def test_sharded_runtime_drain_conserves_across_shards(n_shards):
    reg = _registry()
    st = reg.open_sharded("cit-HepPh", "kmatrix", 64, seed=0,
                          n_shards=n_shards)
    rt = Runtime(queue_capacity=4, publish_policy="every:2", reservoir_k=0,
                 poll_s=0.01)
    handles = attach_shards(rt, st)
    rt.start()
    assert rt.join_pumps(120)
    rt.stop(drain=True)
    cons = sharded_conservation(handles, st.stream.spec.n_edges)
    assert cons["conservation_ok"], cons
    assert cons["dropped_edges"] == 0
    assert cons["published_edges"] == st.stream.spec.n_edges
    # and the merged result is STILL the single-sketch replay
    stream, oracle = _single_shot()
    merged = st.merged_snapshot()
    np.testing.assert_array_equal(np.asarray(merged.sketch.pool),
                                  np.asarray(oracle.pool))


def test_sharded_crash_resume_conserves_and_serves_exactly(tmp_path):
    """Satellite acceptance: kill K shards mid-stream at DIFFERENT offsets,
    restore each from the shard manifest's per-shard checkpoints into a
    fresh registry, drain — per-shard conservation holds and the restored
    registry serves engine == direct answers, with the merged state
    bit-identical to a never-crashed single sketch."""
    ckpt = str(tmp_path / "ckpt")
    reg_a = _registry()
    st_a = reg_a.open_sharded("cit-HepPh", "kmatrix", 64, seed=0, n_shards=3)
    rt_a = Runtime(queue_capacity=2, publish_policy="every:2", reservoir_k=0,
                   checkpoint_dir=ckpt, checkpoint_every=1, poll_s=0.01)
    # different throttles drive the shards to different stream offsets
    handles_a = attach_shards(rt_a, st_a, throttle_s=[0.01, 0.05, 0.09])
    rt_a.start()
    _wait(lambda: all(h.worker.metrics.ingested_batches >= 1
                      for h in handles_a))
    _wait(lambda: handles_a[0].worker.metrics.ingested_batches >= 3)
    rt_a.kill()
    offsets = [s.offset for s in st_a.shards]
    assert any(o < st_a.stream.num_batches for o in offsets), \
        "kill was not mid-stream"

    manifest = read_shard_manifest(ckpt)
    assert manifest["n_shards"] == 3
    assert len(manifest["shard_tenant_ids"]) == 3

    reg_b = _registry()
    st_b = reg_b.open_sharded("cit-HepPh", "kmatrix", 64, seed=0,
                              n_shards=manifest["n_shards"],
                              shard_seed=manifest["shard_seed"])
    rt_b = Runtime(queue_capacity=4, publish_policy="every:2", reservoir_k=0,
                   checkpoint_dir=ckpt, poll_s=0.01)
    handles_b = attach_shards(rt_b, st_b, restore=True)
    restored_offsets = [s.offset for s in st_b.shards]
    assert any(o > 0 for o in restored_offsets), \
        "restore must resume mid-stream, not replay from scratch"
    rt_b.start()
    assert rt_b.join_pumps(120)
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

    # engine == direct on the restored registry's live snapshot
    engine = ShardedQueryEngine(QueryEngine(min_bucket=8))
    snap = st_b.snapshot
    reqs = synth_requests(32, mix_for_sketch("kmatrix"),
                          n_nodes=stream.spec.n_nodes, seed=11,
                          heavy_universe=256, heavy_threshold=5.0)
    got = [r.value for r in engine.execute(snap, reqs)]
    want = sharded_direct_answers(snap, reqs)
    for g, w in zip(got, want):
        assert _values_match(g, w)


def test_attach_shards_rejects_mismatched_manifest(tmp_path):
    ckpt = str(tmp_path / "ckpt")
    reg = _registry()
    st = reg.open_sharded("cit-HepPh", "kmatrix", 64, seed=0, n_shards=2)
    rt = Runtime(queue_capacity=4, publish_policy="every:2", reservoir_k=0,
                 checkpoint_dir=ckpt, checkpoint_every=1, poll_s=0.01)
    attach_shards(rt, st, max_batches=1)
    rt.start()
    rt.join_pumps(120)
    rt.stop(drain=True)

    other = _registry().open_sharded("cit-HepPh", "kmatrix", 64, seed=0,
                                     n_shards=3)
    rt2 = Runtime(queue_capacity=4, reservoir_k=0, checkpoint_dir=ckpt,
                  poll_s=0.01)
    with pytest.raises(ValueError, match="manifest"):
        attach_shards(rt2, other, restore=True)
