"""Serving benchmark: mixed-query workload against a live-ingesting kMatrix.

The BENCH trajectory's serving row.  Measures, in one process:

  * open-loop QPS and p50/p99 latency for a mixed edge-freq / reachability /
    node-aggregate / path / heavy-node workload, while the tenant's ingest
    loop keeps consuming the stream between query batches (publishing a new
    epoch every ``--publish-every`` batches);
  * closure-cache economics: wall time of a reachability batch that must
    rebuild the O(log w) boolean closure (cold) vs one that hits the
    per-(tenant, epoch) cache;
  * exactness: engine answers vs direct ``repro.core.queries`` answers for
    the same snapshot (hard-fails the bench on any mismatch);
  * backend parity (``--sketch-backend pallas`` / REPRO_SKETCH_BACKEND):
    when the tenant runs the width-class accel layout, the warm prefix is
    replayed through the flat-pool backend and both the relayout counters
    and every direct estimate must be bit-identical (hard-fails otherwise).

``--shards K`` serves K hash-band shards: one background runtime worker per
shard, scatter/gather queries through ``ShardedQueryEngine``, and two hard
gates — cross-shard edge conservation (Σ per-shard published + accounted
drops == stream total) and sharded-vs-unsharded exactness (the merge of the
shard sketches must be bit-identical, counters and estimates, to a
single-sketch replay of the same stream).

``--concurrent`` switches ingest to a ``repro.runtime`` background worker:
queries and ingest genuinely overlap, the JSON reports ingest edges/s and
query p50/p99 side by side, the engine-vs-direct gate is re-checked on
EVERY epoch the worker published, and the graceful ``Runtime.stop()`` must
drain with zero unaccounted edges (published + accounted drops == stream
total) — both gates hard-fail the bench.

Emits a single JSON line on stdout (progress goes to stderr):

  PYTHONPATH=src python -m benchmarks.serve_bench --quick [--concurrent]
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np
import jax

from repro.serving import (
    OpenLoopLoadGen,
    QueryEngine,
    SketchRegistry,
    mix_for_sketch,
    synth_requests,
    warm_bucket_ladder,
)
from repro.serving import engine as eng
from repro.serving.gates import (
    conservation_verdict,
    mismatched_indices,
    replay_exactness,
    replay_sketch,
)

def _log(msg: str) -> None:
    print(msg, file=sys.stderr)


def _time_execute(engine: QueryEngine, snapshot, requests) -> float:
    t0 = time.perf_counter()
    engine.execute(snapshot, requests)
    return time.perf_counter() - t0


def _backend_parity_gate(tenant, requests, accel_answers=None) -> dict | None:
    """Hard gate for the width-class (pallas) sketch backend.

    Call only when the tenant's delta is freshly published (so the front
    snapshot holds exactly stream batches ``[0, tenant.offset)``).  Replays
    that prefix through the flat-pool backend and requires (a) the accel
    sketch to be a bit-exact relayout of the flat one, and (b) every direct
    estimate to be bit-identical between the two layouts.  Returns None for
    non-accel tenants.  ``accel_answers`` lets the caller reuse direct
    answers it already computed for ``requests`` on the accel snapshot (the
    per-request oracle rebuilds closures and is the slow half of the gate).
    """
    from repro.core import KMatrixAccel, kmatrix
    from repro.core import kmatrix_accel as kma
    from repro.serving.snapshot import Snapshot

    snap = tenant.snapshot
    if not isinstance(snap.sketch, KMatrixAccel):
        return None
    flat = replay_sketch(kmatrix, kma.to_flat_layout(kma.empty_like(
        snap.sketch)), tenant.stream, tenant.offset)
    relayout_snap = Snapshot(snap.tenant_id + "/relayout", snap.epoch,
                             kma.to_flat_layout(snap.sketch), snap.kind,
                             snap.n_edges)
    if accel_answers is None:
        # baseline answers MUST come from the accel snapshot itself (not
        # the relayout) — the estimate half of the gate exists to catch
        # accel-side query-path bugs, which a flat-vs-flat compare hides
        accel_answers = eng.direct_answers(snap, requests)
    verdict = replay_exactness(relayout_snap, flat, requests,
                               answers=accel_answers)
    if not verdict["ok"]:
        _log(f"BACKEND PARITY FAILURE: "
             f"counters_equal={verdict['counters_equal']} "
             f"estimates_equal={verdict['estimates_equal']}")
    return {
        "backend_parity_counters": verdict["counters_equal"],
        "backend_parity_estimates": verdict["estimates_equal"],
        "backend_parity_ok": verdict["ok"],
    }


def run_serve_bench(*, dataset: str = "cit-HepPh", sketch: str = "kmatrix",
                    budget_kb: int = 256, depth: int = 5, seed: int = 0,
                    scale: float = 1.0, target_qps: float = 2000.0,
                    n_requests: int = 4000, batch_max: int = 512,
                    publish_every: int = 2, warm_batches: int = 8,
                    sketch_backend: str | None = None) -> dict:
    registry = SketchRegistry(depth=depth, scale=scale,
                              sketch_backend=sketch_backend)
    tenant = registry.open(dataset, sketch, budget_kb, seed=seed)
    engine = QueryEngine()

    # leave at least half the stream unread so serving runs against LIVE
    # ingest (the point of the bench), even at tiny --quick scales
    tenant.step(min(warm_batches, max(1, tenant.stream.num_batches // 2)))
    snap = tenant.publish()
    n_nodes = tenant.stream.spec.n_nodes
    _log(f"tenant {tenant.key.tenant_id}: epoch {snap.epoch}, "
         f"{snap.n_edges} edges ingested, universe {n_nodes}")

    mix = mix_for_sketch(sketch)
    requests = synth_requests(n_requests, mix, n_nodes=n_nodes, seed=seed + 7,
                              heavy_universe=min(n_nodes, 1 << 14),
                              heavy_threshold=100.0)

    # ---- warmup: compile the whole bucket ladder off the clock ------------
    warm = synth_requests(max(batch_max, 256), mix, n_nodes=n_nodes, seed=99,
                          heavy_universe=min(n_nodes, 1 << 14),
                          heavy_threshold=100.0)
    warm_bucket_ladder(engine, snap, warm)

    # ---- closure cache: cold rebuild vs hit, same snapshot ----------------
    # Two views, medians of 7 reps each: (a) the cache itself — closure
    # build (blocking) vs cache hit; (b) end-to-end reachability batches on
    # a cleared vs warm cache.  (a) is the invariant the cache exists for;
    # (b) shows what a client sees (at small conn widths the cascade is
    # cheap, so (b) compresses toward 1x while (a) stays orders-of-magnitude).
    t_build = t_lookup = t_cold = t_hit = 0.0
    if mix.reach > 0:  # Type I sketches have no closure to cache
        engine.closures.get(snap, None)  # compile the cascade off the clock
        build, lookup = [], []
        for _ in range(7):
            engine.closures.clear()
            t0 = time.perf_counter()
            jax.block_until_ready(engine.closures.get(snap, None))
            build.append(time.perf_counter() - t0)
            t0 = time.perf_counter()
            engine.closures.get(snap, None)
            lookup.append(time.perf_counter() - t0)
        t_build = float(np.median(build))
        t_lookup = float(np.median(lookup))

        reach_reqs = [eng.reach(int(a), int(b)) for a, b in zip(
            np.random.default_rng(3).integers(0, n_nodes, 64),
            np.random.default_rng(4).integers(0, n_nodes, 64))]
        engine.execute(snap, reach_reqs)  # compile lookup path off the clock
        cold, hit = [], []
        for _ in range(7):
            engine.closures.clear()
            cold.append(_time_execute(engine, snap, reach_reqs))
            hit.append(_time_execute(engine, snap, reach_reqs))
        t_cold = float(np.median(cold))
        t_hit = float(np.median(hit))
        _log(f"closure build {t_build*1e3:.3f} ms vs cache hit "
             f"{t_lookup*1e3:.4f} ms ({t_build/max(t_lookup, 1e-9):.0f}x); "
             f"reach batch cold {t_cold*1e3:.2f} ms vs warm {t_hit*1e3:.2f} ms")

    # ---- exactness: engine vs direct module-level answers -----------------
    check = requests[:200]
    got = [r.value for r in engine.execute(snap, check)]
    want = eng.direct_answers(snap, check)
    bad = mismatched_indices(got, want)
    matches = not bad
    if bad:
        _log(f"MISMATCH engine vs direct at request indices {bad[:10]}")

    # ---- accel backend: bit-exact vs the flat layout on the same prefix ---
    parity = _backend_parity_gate(tenant, check[:64], accel_answers=want[:64])

    # ---- open-loop mixed workload against the LIVE tenant -----------------
    epoch0 = tenant.epoch
    batches_between = [0]

    def live_ingest() -> None:
        stepped = tenant.step(1)
        batches_between[0] += stepped
        # key off this call's progress, not the cumulative count: once the
        # stream drains, a frozen total would either publish after every
        # served batch (thrashing the closure cache) or never again
        if stepped and batches_between[0] % publish_every == 0:
            tenant.publish()

    loadgen = OpenLoopLoadGen(target_qps=target_qps, batch_max=batch_max)
    report = loadgen.run(engine, lambda: tenant.snapshot, requests,
                         between_batches=live_ingest)
    _log(report.to_json())

    record = {
        "bench": "serve_mixed",
        "dataset": dataset,
        "sketch": sketch,
        "sketch_backend": registry.sketch_backend,
        "budget_kb": budget_kb,
        "depth": depth,
        "offered_qps": report.offered_qps,
        "achieved_qps": round(report.achieved_qps, 1),
        "p50_ms": round(report.p50_ms, 3),
        "p99_ms": round(report.p99_ms, 3),
        "n_requests": report.n_requests,
        "n_batches": report.n_batches,
        "epochs_published": tenant.epoch - epoch0,
        "ingest_batches_during_serve": batches_between[0],
        "closure_build_ms": round(t_build * 1e3, 4),
        "closure_cache_hit_ms": round(t_lookup * 1e3, 4),
        "closure_cache_speedup": round(t_build / max(t_lookup, 1e-9), 1),
        "reach_batch_cold_ms": round(t_cold * 1e3, 3),
        "reach_batch_warm_ms": round(t_hit * 1e3, 3),
        "engine_matches_direct": bool(matches),
        "overflow_edges": tenant.buffer.overflow_edges,
        **(parity or {}),
        **{f"engine_{k}": v for k, v in engine.stats.items()},
    }
    return record


def run_serve_bench_concurrent(*, dataset: str = "cit-HepPh",
                               sketch: str = "kmatrix", budget_kb: int = 256,
                               depth: int = 5, seed: int = 0,
                               scale: float = 1.0,
                               target_qps: float = 2000.0,
                               n_requests: int = 4000, batch_max: int = 512,
                               publish_every: int = 2, warm_batches: int = 8,
                               queue_capacity: int = 64,
                               backpressure: str = "block",
                               publish_policy: str = "",
                               epoch_check_requests: int = 32,
                               sketch_backend: str | None = None,
                               runtime_backend: str = "thread") -> dict:
    """Concurrent regime: loadgen in the main thread, ingest in a
    ``repro.runtime`` worker (thread or process execution backend).  Gates
    (both hard-fail): engine == direct on every published epoch;
    conservation (published + drops == stream total) after a graceful
    drain."""
    from repro.runtime import Runtime

    registry = SketchRegistry(depth=depth, scale=scale,
                              sketch_backend=sketch_backend)
    tenant = registry.open(dataset, sketch, budget_kb, seed=seed)
    engine = QueryEngine()

    tenant.step(min(warm_batches, max(1, tenant.stream.num_batches // 2)))
    snap = tenant.publish()
    n_nodes = tenant.stream.spec.n_nodes
    _log(f"tenant {tenant.key.tenant_id}: warm epoch {snap.epoch}, "
         f"{snap.n_edges} edges ingested, universe {n_nodes}")

    mix = mix_for_sketch(sketch)
    requests = synth_requests(n_requests, mix, n_nodes=n_nodes, seed=seed + 7,
                              heavy_universe=min(n_nodes, 1 << 14),
                              heavy_threshold=100.0)
    warm = synth_requests(max(batch_max, 256), mix, n_nodes=n_nodes, seed=99,
                          heavy_universe=min(n_nodes, 1 << 14),
                          heavy_threshold=100.0)
    warm_bucket_ladder(engine, snap, warm)

    # every epoch the worker publishes lands here (snapshots are immutable,
    # so holding them costs only references) and is exactness-gated below
    published: list = [snap]
    runtime = Runtime(queue_capacity=queue_capacity,
                      backpressure=backpressure,
                      publish_policy=publish_policy
                      or f"every:{publish_every}",
                      backend=runtime_backend)
    runtime.attach(tenant, on_publish=published.append)
    runtime.start(pumps=False)
    runtime.wait_ready()  # process children build + warm off the clock
    runtime.start_pumps()

    loadgen = OpenLoopLoadGen(target_qps=target_qps, batch_max=batch_max)
    t0 = time.perf_counter()
    report = loadgen.run(engine, lambda: tenant.snapshot, requests)
    serve_wall_s = time.perf_counter() - t0
    mid = runtime.metrics()[tenant.key.tenant_id]
    edges_during_serve = mid["ingested_edges"]
    _log(report.to_json())

    runtime.join_pumps()  # offer the whole stream, then drain-and-stop
    final = runtime.stop(drain=True)[tenant.key.tenant_id]

    # ---- gate 1: engine vs direct on EVERY published epoch ----------------
    check = requests[:epoch_check_requests]
    mismatched_epochs = []
    for s in published:
        got = [r.value for r in engine.execute(s, check)]
        want = eng.direct_answers(s, check)
        if mismatched_indices(got, want):
            mismatched_epochs.append(s.epoch)
    if mismatched_epochs:
        _log(f"MISMATCH engine vs direct at epochs {mismatched_epochs}")

    # ---- gate 2: conservation after graceful drain ------------------------
    cons = conservation_verdict(final["published_edges"],
                                final["dropped_edges"],
                                tenant.stream.spec.n_edges,
                                final["unaccounted_edges"])
    if not cons["conservation_ok"]:
        _log(f"CONSERVATION FAILURE: published {final['published_edges']} "
             f"+ dropped {final['dropped_edges']} != stream "
             f"{cons['stream_total_edges']} "
             f"(unaccounted {final['unaccounted_edges']})")

    return {
        "bench": "serve_concurrent",
        "dataset": dataset,
        "sketch": sketch,
        "sketch_backend": registry.sketch_backend,
        "budget_kb": budget_kb,
        "depth": depth,
        "runtime_backend": runtime.backend.name,
        "worker_placement": runtime.backend.placement,
        "backpressure": backpressure,
        "publish_policy": publish_policy or f"every:{publish_every}",
        "offered_qps": report.offered_qps,
        "achieved_qps": round(report.achieved_qps, 1),
        "p50_ms": round(report.p50_ms, 3),
        "p99_ms": round(report.p99_ms, 3),
        "n_requests": report.n_requests,
        "n_batches": report.n_batches,
        "ingest_edges_during_serve": edges_during_serve,
        "ingest_edges_per_s_during_serve": round(
            edges_during_serve / max(serve_wall_s, 1e-9), 1),
        "ingest_edges_per_s_ewma": mid["edges_per_s_ewma"],
        "epochs_published": len(published) - 1,
        "epochs_checked": len(published),
        "publishes": final["publishes"],
        "mean_publish_latency_ms": final["mean_publish_latency_ms"],
        "max_queue_depth": final["max_queue_depth"],
        "dropped_edges": final["dropped_edges"],
        # accel-backend scatter-fallback volume (0 under the flat backend):
        # capacity regressions surface here instead of as silent slow ingest
        "overflow_edges": final["overflow_edges"],
        "published_edges": final["published_edges"],
        "stream_total_edges": cons["stream_total_edges"],
        "unaccounted_edges": final["unaccounted_edges"],
        "conservation_ok": cons["conservation_ok"],
        "engine_matches_direct": not mismatched_epochs,
        **{f"engine_{k}": v for k, v in engine.stats.items()},
    }


def run_serve_bench_sharded(*, dataset: str = "cit-HepPh",
                            sketch: str = "kmatrix", budget_kb: int = 256,
                            depth: int = 5, seed: int = 0,
                            scale: float = 1.0, target_qps: float = 2000.0,
                            n_requests: int = 4000, batch_max: int = 512,
                            publish_every: int = 2, warm_batches: int = 4,
                            n_shards: int = 4, queue_capacity: int = 64,
                            backpressure: str = "block",
                            publish_policy: str = "",
                            epoch_check_requests: int = 64,
                            sketch_backend: str | None = None,
                            runtime_backend: str = "thread",
                            ingest_repeats: int = 1) -> dict:
    """Sharded regime: K runtime ingest workers (one per hash-band shard,
    on the thread OR process execution backend) under live scatter/gather
    query load.  Two hard gates (both fail the bench): cross-shard edge
    conservation (Σ per-shard published + accounted drops == stream total)
    and sharded-vs-unsharded exactness (the merge of the shard sketches
    must be bit-identical — counters and direct estimates — to a
    single-sketch replay of the same stream, which the source-hash-band
    routing guarantees)."""
    from repro.runtime import Runtime
    from repro.serving import (ShardedQueryEngine, attach_shards,
                               measure_sharded_ingest, sharded_conservation,
                               sharded_direct_answers, warm_ingest_shapes)

    registry = SketchRegistry(depth=depth, scale=scale,
                              sketch_backend=sketch_backend)
    tenant = registry.open_sharded(dataset, sketch, budget_kb, seed=seed,
                                   n_shards=n_shards)
    engine = ShardedQueryEngine()
    stream = tenant.stream

    # ---- dedicated ingest throughput: backlog drain, no query load --------
    # a THROWAWAY tenant (fresh registry, same config) so the serve-phase
    # tenant below still owns its whole stream; this is the scaling number
    # BENCH_sharded.json / BENCH_process.json chart against K
    # best-of-N: the quick-scale drain lasts ~150 ms, so a single sample is
    # scheduler noise on a small box; every repeat pays the full spawn/warm
    # cost with a FRESH throwaway tenant and the best drain is the capacity
    # number (identical treatment for every backend, so ratios stay fair)
    dedicated = None
    for _ in range(max(1, ingest_repeats)):
        d = measure_sharded_ingest(
            SketchRegistry(depth=depth, scale=scale,
                           sketch_backend=sketch_backend).open_sharded(
                dataset, sketch, budget_kb, seed=seed, n_shards=n_shards),
            backend=runtime_backend)
        if not d["conserved"]:
            _log(f"DEDICATED INGEST CONSERVATION FAILURE: {d}")
        if dedicated is None or d["edges_per_s"] > dedicated["edges_per_s"]:
            dedicated = d
    _log(f"dedicated ingest drain x{n_shards} (best of {ingest_repeats}): "
         f"{dedicated['edges_per_s']:,.0f} edges/s "
         f"({dedicated['ingested_edges']} edges, {dedicated['wall_s']}s)")
    warm_ingest_shapes(tenant)  # serve-phase shard shapes, off the clock

    tenant.step(min(warm_batches, max(1, stream.num_batches // 2)))
    snap = tenant.publish()
    n_nodes = stream.spec.n_nodes
    _log(f"sharded tenant {tenant.key.tenant_id} x{n_shards}: epochs "
         f"{snap.epochs}, {snap.n_edges} edges warm, universe {n_nodes}")

    mix = mix_for_sketch(sketch)
    requests = synth_requests(n_requests, mix, n_nodes=n_nodes, seed=seed + 7,
                              heavy_universe=min(n_nodes, 1 << 14),
                              heavy_threshold=100.0)
    warm = synth_requests(max(batch_max, 256), mix, n_nodes=n_nodes, seed=99,
                          heavy_universe=min(n_nodes, 1 << 14),
                          heavy_threshold=100.0)
    warm_bucket_ladder(engine, snap, warm)

    # ---- exactness: scatter/gather engine vs sharded direct oracle --------
    check = requests[:epoch_check_requests]
    got = [r.value for r in engine.execute(snap, check)]
    want = sharded_direct_answers(snap, check)
    bad = mismatched_indices(got, want)
    matches = not bad
    if bad:
        _log(f"MISMATCH sharded engine vs direct at request indices "
             f"{bad[:10]}")

    # ---- serve under live per-shard background ingest ---------------------
    runtime = Runtime(queue_capacity=queue_capacity,
                      backpressure=backpressure,
                      publish_policy=publish_policy
                      or f"every:{publish_every}",
                      coalesce_batches=max(4, n_shards),
                      coalesce_target=stream.batch_size,
                      backend=runtime_backend)
    handles = attach_shards(runtime, tenant)
    runtime.start(pumps=False)
    runtime.wait_ready()  # process children build + warm off the clock
    runtime.start_pumps()
    loadgen = OpenLoopLoadGen(target_qps=target_qps, batch_max=batch_max)
    t0 = time.perf_counter()
    report = loadgen.run(engine, lambda: tenant.snapshot, requests)
    serve_wall_s = time.perf_counter() - t0
    edges_during_serve = sum(m["ingested_edges"]
                             for m in runtime.metrics().values())
    _log(report.to_json())

    runtime.join_pumps()
    t_ingest0 = time.perf_counter()
    runtime.stop(drain=True)
    drain_s = time.perf_counter() - t_ingest0

    # ---- gate 1: cross-shard conservation ---------------------------------
    cons = sharded_conservation(handles, stream.spec.n_edges)
    if not cons["conservation_ok"]:
        _log(f"SHARDED CONSERVATION FAILURE: {cons}")

    # ---- gate 2: merged shards == single-sketch replay, bit-exact ---------
    # Only meaningful with zero drops: under drop_oldest the replay would
    # ingest the accounted drops the shards legitimately never saw, so the
    # mismatch would be the backpressure policy, not a routing break.
    if cons["dropped_edges"] == 0:
        merged = tenant.merged_snapshot()
        replay = replay_sketch(tenant.mod,
                               tenant.mod.empty_like(merged.sketch),
                               stream, stream.num_batches)
        verdict = replay_exactness(merged, replay, check)
        counters_equal = verdict["counters_equal"]
        estimates_equal = verdict["estimates_equal"]
        sharded_exact = verdict["ok"]
        if not sharded_exact:
            _log(f"SHARDED EXACTNESS FAILURE: "
                 f"counters_equal={counters_equal} "
                 f"estimates_equal={estimates_equal}")
    else:
        counters_equal = estimates_equal = sharded_exact = None
        _log(f"sharded exactness gate skipped: {cons['dropped_edges']} "
             "edges dropped under backpressure (accounted by the "
             "conservation gate); a full-stream replay is not comparable")

    total_edges = cons["published_edges"]
    return {
        "bench": "serve_sharded",
        "dataset": dataset,
        "sketch": sketch,
        "sketch_backend": registry.sketch_backend,
        "budget_kb": budget_kb,
        "depth": depth,
        "n_shards": n_shards,
        "runtime_backend": runtime.backend.name,
        "worker_placement": runtime.backend.placement,
        "backpressure": backpressure,
        "publish_policy": publish_policy or f"every:{publish_every}",
        "offered_qps": report.offered_qps,
        "achieved_qps": round(report.achieved_qps, 1),
        "p50_ms": round(report.p50_ms, 3),
        "p99_ms": round(report.p99_ms, 3),
        "n_requests": report.n_requests,
        "ingest_edges_during_serve": edges_during_serve,
        "ingest_edges_per_s_during_serve": round(
            edges_during_serve / max(serve_wall_s, 1e-9), 1),
        # pure concurrent-ingest capacity (backlog drain, no query load) —
        # the honest scaling-vs-K number; the during-serve rate above is
        # dominated by query contention on shared cores
        "ingest_edges_per_s_dedicated": dedicated["edges_per_s"],
        "dedicated_ingest_conserved": dedicated["conserved"],
        "drain_s": round(drain_s, 3),
        "epochs": list(tenant.epochs),
        "published_edges": total_edges,
        "dropped_edges": cons["dropped_edges"],
        "per_shard_published": cons["per_shard_published"],
        "stream_total_edges": cons["stream_total_edges"],
        "conservation_ok": cons["conservation_ok"],
        # None (not False) when drops made the replay incomparable
        "sharded_counters_equal": counters_equal,
        "sharded_estimates_equal": estimates_equal,
        "sharded_exact": sharded_exact,
        "engine_matches_direct": bool(matches),
        **{f"engine_{k}": v for k, v in engine.stats.items()},
    }


def main() -> None:
    from repro.launch.host import init_compile_cache

    init_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="cit-HepPh")
    ap.add_argument("--sketch", default="kmatrix")
    ap.add_argument("--budget-kb", type=int, default=256)
    ap.add_argument("--depth", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--scale", type=float, default=1.0)
    ap.add_argument("--qps", type=float, default=2000.0)
    ap.add_argument("--n-requests", type=int, default=4000)
    ap.add_argument("--batch-max", type=int, default=512)
    ap.add_argument("--publish-every", type=int, default=2)
    ap.add_argument("--sketch-backend", default="",
                    choices=["", "flat", "pallas"],
                    help="kmatrix layout (default: $REPRO_SKETCH_BACKEND, "
                         "else platform pick)")
    ap.add_argument("--concurrent", action="store_true",
                    help="background runtime ingest concurrent with queries")
    ap.add_argument("--shards", type=int, default=0,
                    help="serve K hash-band shards (one runtime ingest "
                         "worker per shard, scatter/gather queries); gates "
                         "cross-shard conservation AND merged-vs-unsharded "
                         "bit-exactness")
    ap.add_argument("--backpressure", default="block",
                    choices=["block", "drop_oldest"])
    ap.add_argument("--publish-policy", default="",
                    help="every:N | interval:S | drain[:W]")
    ap.add_argument("--queue-capacity", type=int, default=64)
    ap.add_argument("--runtime-backend", default="thread",
                    help="execution backend for ingest workers: thread, "
                         "process (spawn children owning their sketches), "
                         "or socket[:HOST:PORT,...] (workers across TCP — "
                         "self-hosted loopback children, or stream_ingest "
                         "--listen hosts); process/socket need "
                         "--concurrent or --shards")
    ap.add_argument("--quick", action="store_true",
                    help="small scale + short run (CI)")
    args = ap.parse_args()
    _valid_backends = ("thread", "process", "socket")
    if args.runtime_backend not in _valid_backends \
            and not args.runtime_backend.startswith("socket:"):
        ap.error(f"--runtime-backend must be one of {_valid_backends} or "
                 f"socket:HOST:PORT[,...], got {args.runtime_backend!r}")
    if args.runtime_backend != "thread" and not (args.concurrent
                                                 or args.shards):
        ap.error(f"--runtime-backend {args.runtime_backend} requires "
                 "--concurrent or --shards (the plain bench has no "
                 "background runtime)")
    if args.quick:
        args.scale = min(args.scale, 0.1)
        args.n_requests = min(args.n_requests, 1000)
        args.qps = min(args.qps, 1000.0)

    if args.shards:
        record = run_serve_bench_sharded(
            dataset=args.dataset, sketch=args.sketch,
            budget_kb=args.budget_kb, depth=args.depth, seed=args.seed,
            scale=args.scale, target_qps=args.qps,
            n_requests=args.n_requests, batch_max=args.batch_max,
            publish_every=args.publish_every, n_shards=args.shards,
            queue_capacity=args.queue_capacity,
            backpressure=args.backpressure,
            publish_policy=args.publish_policy,
            sketch_backend=args.sketch_backend or None,
            runtime_backend=args.runtime_backend)
        print(json.dumps(record))
        if not (record["engine_matches_direct"]
                and record["conservation_ok"]
                and record["sharded_exact"] is not False
                and record["dedicated_ingest_conserved"]):
            sys.exit(1)
        return

    if args.concurrent:
        record = run_serve_bench_concurrent(
            dataset=args.dataset, sketch=args.sketch,
            budget_kb=args.budget_kb, depth=args.depth, seed=args.seed,
            scale=args.scale, target_qps=args.qps,
            n_requests=args.n_requests, batch_max=args.batch_max,
            publish_every=args.publish_every,
            queue_capacity=args.queue_capacity,
            backpressure=args.backpressure,
            publish_policy=args.publish_policy,
            sketch_backend=args.sketch_backend or None,
            runtime_backend=args.runtime_backend)
        print(json.dumps(record))
        if not (record["engine_matches_direct"]
                and record["conservation_ok"]):
            sys.exit(1)
        return

    record = run_serve_bench(
        dataset=args.dataset, sketch=args.sketch, budget_kb=args.budget_kb,
        depth=args.depth, seed=args.seed, scale=args.scale,
        target_qps=args.qps, n_requests=args.n_requests,
        batch_max=args.batch_max, publish_every=args.publish_every,
        sketch_backend=args.sketch_backend or None)
    print(json.dumps(record))
    if not (record["engine_matches_direct"]
            and record.get("backend_parity_ok", True)):
        sys.exit(1)


if __name__ == "__main__":
    main()
