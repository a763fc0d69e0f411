"""End-to-end online serving driver: ingest + snapshot publishing + queries.

Two ingest modes share the same tenant, engine and load generator:

  cooperative (default)   ingest advances between served query batches in
      ONE thread — the PR 1 behaviour, kept as the deterministic baseline.

  --background-ingest     ingest runs in a ``repro.runtime`` worker thread
      behind a bounded queue (``--backpressure``), publishing epochs under
      ``--publish-policy``, while the load generator fires queries from the
      main thread the whole time — queries and ingest genuinely overlap.
      The summary gains runtime metrics (ingest edges/s, queue depth,
      publish latency) and a conservation report (offered == published +
      accounted drops); ``--checkpoint-dir`` adds crash-safe checkpoints
      and ``--restore`` resumes from the latest one.

  --runtime-backend process   (with --background-ingest) run each ingest
      worker in a spawn-safe child process that owns its sketch
      (DESIGN.md §Runtime §Backends): published epochs ship back into this
      process's snapshot buffer, so queries serve locally while K-shard
      ingest scales past the GIL.  Checkpoints stay interchangeable with
      the thread backend.  SIGTERM/SIGINT trigger a graceful drain (final
      epoch + checkpoint flushed) before exit in every background mode.

  --shards K              (with --background-ingest) sharded serving: edges
      route to K independent sketch shards by a source-node hash band; one
      worker + queue per shard, each publishing epochs independently, and
      queries scatter/gather through ``ShardedQueryEngine``.  With
      ``--checkpoint-dir`` each shard checkpoints separately and a shard
      manifest records the topology; ``--restore`` validates it and resumes
      every shard from its own offset.  The summary gains per-shard
      published counts and a cross-shard conservation verdict.

  --serve HOST:PORT       (with --background-ingest) put the engine behind
      a ``repro.net`` TCP query server with admission control (bounded
      in-flight budget via --max-inflight, per-tenant token-bucket rate
      limiting via --tenant-qps) and drive the measurement over real
      sockets: --connections concurrent open-loop client connections
      (``NetLoadGen``).  ``--n-requests 0`` serves until SIGTERM/SIGINT
      instead — the standing front-end a remote
      ``python -m repro.serving.loadgen --connect`` client can load.
      Combine with ``--runtime-backend socket:HOST:PORT`` to place the
      ingest workers on ``stream_ingest --listen`` hosts: a fully
      networked ingest+serve deployment (DESIGN.md §Net).

Prints a JSON summary line (QPS, p50/p99 latency, epochs) on completion.

  python -m repro.launch.query_serve --dataset cit-HepPh --sketch kmatrix \
      --budget-kb 256 --qps 2000 --n-requests 8000 [--scale 0.25] \
      [--background-ingest] [--backpressure drop_oldest] \
      [--publish-policy interval:0.25] [--serve 127.0.0.1:7311]
"""
from __future__ import annotations

import argparse
import json
import os
import signal
import sys
import time

from repro.launch.host import device_info, init_compile_cache
from repro.serving import (
    OpenLoopLoadGen,
    QueryEngine,
    SketchRegistry,
    WorkloadMix,
    mix_for_sketch,
    synth_requests,
    warm_bucket_ladder,
)
from repro.serving.gates import conservation_verdict


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="cit-HepPh")
    ap.add_argument("--sketch", default="kmatrix",
                    choices=["countmin", "gsketch", "tcm", "gmatrix",
                             "kmatrix"])
    ap.add_argument("--budget-kb", type=int, default=256)
    ap.add_argument("--depth", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--scale", type=float, default=1.0)
    ap.add_argument("--partitioner", default="banded",
                    choices=["banded", "greedy", "auto"])
    ap.add_argument("--sketch-backend", default="",
                    choices=["", "flat", "pallas"],
                    help="kmatrix physical layout: flat XLA scatter pool or "
                         "width-class Pallas MXU layout (default: "
                         "$REPRO_SKETCH_BACKEND, else pallas on TPU / flat "
                         "elsewhere)")
    ap.add_argument("--qps", type=float, default=2000.0)
    ap.add_argument("--n-requests", type=int, default=8000)
    ap.add_argument("--batch-max", type=int, default=512)
    ap.add_argument("--publish-every", type=int, default=4,
                    help="cooperative mode: ingest batches between publishes")
    ap.add_argument("--warm-batches", type=int, default=4,
                    help="ingest batches before serving starts")
    ap.add_argument("--mix", default="",
                    help="comma list family=weight, e.g. "
                         "'edge_freq=0.7,reach=0.3' (default: built-in mix)")
    # ---- background ingest runtime (repro.runtime) ----
    ap.add_argument("--background-ingest", action="store_true",
                    help="ingest in a worker thread behind a bounded queue; "
                         "queries run truly concurrently")
    ap.add_argument("--runtime-backend", default="thread",
                    help="execution backend for ingest workers: thread "
                         "(in-process, GIL-shared), process (spawn "
                         "children owning their sketches — K-shard ingest "
                         "scales past the GIL), or "
                         "socket[:HOST:PORT,...] (workers across TCP: "
                         "self-hosted loopback children, or stream_ingest "
                         "--listen hosts when addresses are given); "
                         "requires --background-ingest")
    ap.add_argument("--publish-mode", default="delta",
                    choices=["delta", "full"],
                    help="remote-backend snapshot publication: 'delta' "
                         "(default) ships only the per-epoch sketch delta, "
                         "sparse-encoded; 'full' ships whole fronts every "
                         "epoch (pre-v3 behaviour, kept for A/B benching)")
    ap.add_argument("--shards", type=int, default=1,
                    help="serve K hash-band shards: one ingest worker + "
                         "queue per shard, scatter/gather queries "
                         "(requires --background-ingest)")
    ap.add_argument("--shard-seed", type=int, default=0,
                    help="seed of the shard routing hash (must match the "
                         "manifest when restoring a sharded checkpoint)")
    ap.add_argument("--queue-capacity", type=int, default=64)
    ap.add_argument("--backpressure", default="block",
                    choices=["block", "drop_oldest", "spill"])
    ap.add_argument("--publish-policy", default="",
                    help="every:N | interval:S | drain[:W] "
                         "(default: every:<--publish-every>)")
    ap.add_argument("--spill-dir", default="",
                    help="required for --backpressure spill")
    ap.add_argument("--checkpoint-dir", default="",
                    help="enable crash-safe checkpoints in background mode")
    ap.add_argument("--checkpoint-every", type=int, default=16,
                    help="batches between checkpoints (with --checkpoint-dir)")
    ap.add_argument("--restore", action="store_true",
                    help="resume from the latest checkpoint in "
                         "--checkpoint-dir before serving")
    ap.add_argument("--ingest-dedup", action="store_true",
                    help="pre-aggregate duplicate (src, dst) rows on the "
                         "host before each coalesced ingest dispatch — "
                         "bit-exact (counters are linear), fewer device "
                         "scatter rows on skewed streams")
    ap.add_argument("--no-donate", action="store_true",
                    help="disable jit buffer donation in the ingest path "
                         "(sets REPRO_DONATE=0 for this process and its "
                         "workers; A/B and debugging aid)")
    # ---- network front-end (repro.net) ----
    ap.add_argument("--serve", default="", metavar="HOST:PORT",
                    help="serve queries over TCP with admission control; "
                         "measurement runs through --connections real "
                         "client connections (requires "
                         "--background-ingest); --n-requests 0 serves "
                         "until signalled instead")
    ap.add_argument("--connections", type=int, default=4,
                    help="with --serve: concurrent loadgen client "
                         "connections")
    ap.add_argument("--max-inflight", type=int, default=4096,
                    help="with --serve: admission budget — requests queued "
                         "or executing before fast-reject")
    ap.add_argument("--tenant-qps", type=float, default=0.0,
                    help="with --serve: per-tenant token-bucket rate limit "
                         "(0 = off)")
    ap.add_argument("--auth-token", default="",
                    help="with --serve: shared connection token (default: "
                         "$KMATRIX_NET_TOKEN); REQUIRED to serve on a "
                         "non-loopback address — clients present it via "
                         "loadgen --auth-token / the same env var")
    # ---- telemetry exposition (repro.obs) ----
    ap.add_argument("--metrics-json", default="", metavar="PATH",
                    help="periodically dump the merged metrics hub to PATH "
                         "as JSON (atomic replace; same payload as the "
                         "'metrics' wire frame — dashboard/CI food)")
    ap.add_argument("--metrics-interval-s", type=float, default=1.0,
                    help="with --metrics-json: seconds between dumps")
    ap.add_argument("--span-log", default="", metavar="PATH",
                    help="on exit, append the bounded trace-span ring "
                         "(ingest enqueue->adopt, query accept->reply) to "
                         "PATH as JSONL")
    args = ap.parse_args(argv)
    _valid_backends = ("thread", "process", "socket")
    if args.runtime_backend not in _valid_backends \
            and not args.runtime_backend.startswith("socket:"):
        ap.error(f"--runtime-backend must be one of {_valid_backends} or "
                 f"socket:HOST:PORT[,...], got {args.runtime_backend!r}")
    if not args.background_ingest:
        # these only take effect inside the runtime; silently ignoring them
        # would serve a different run than the one asked for
        for flag, is_set in [("--restore", args.restore),
                             ("--checkpoint-dir", bool(args.checkpoint_dir)),
                             ("--spill-dir", bool(args.spill_dir)),
                             ("--backpressure",
                              args.backpressure != "block"),
                             ("--publish-policy", bool(args.publish_policy)),
                             ("--runtime-backend",
                              args.runtime_backend != "thread"),
                             ("--queue-capacity",
                              args.queue_capacity != 64),
                             ("--ingest-dedup", args.ingest_dedup),
                             ("--serve", bool(args.serve))]:
            if is_set:
                ap.error(f"{flag} requires --background-ingest")
    if not args.serve:
        for flag, is_set in [("--connections", args.connections != 4),
                             ("--max-inflight", args.max_inflight != 4096),
                             ("--tenant-qps", args.tenant_qps != 0.0),
                             ("--auth-token", bool(args.auth_token))]:
            if is_set:
                ap.error(f"{flag} requires --serve")
    if args.shards < 1:
        ap.error("--shards must be >= 1")
    if args.shards > 1 and not args.background_ingest:
        # sharding exists to parallelize ingest; a cooperative single
        # thread stepping K shards round-robin would just serve the same
        # stream slower
        ap.error("--shards > 1 requires --background-ingest")
    if args.restore and not args.checkpoint_dir:
        ap.error("--restore requires --checkpoint-dir")
    if args.backpressure == "spill" and not args.spill_dir:
        # fail at parse time, not after the multi-second jit warm-up
        ap.error("--backpressure spill requires --spill-dir")
    return args


def build_mix(args) -> WorkloadMix:
    if not args.mix:
        return mix_for_sketch(args.sketch)
    weights = {k: 0.0 for k in WorkloadMix().normalized()}
    for part in args.mix.split(","):
        k, v = part.split("=")
        if k.strip() not in weights:
            raise SystemExit(f"unknown query family {k.strip()!r} in --mix")
        weights[k.strip()] = float(v)
    return WorkloadMix(**weights)


def install_graceful_drain(runtime) -> None:
    """SIGTERM/SIGINT -> graceful drain-and-stop, then exit 128+signum.

    An orchestrator's shutdown (or a terminal Ctrl-C) must not be a crash:
    the runtime drains its queues, publishes the final epoch and flushes a
    final checkpoint (when checkpointing is configured — the worker's drain
    path does that) before the process exits, so the next ``--restore``
    resumes from the shutdown point instead of replaying from the last
    periodic checkpoint.  Worker failures discovered during the drain are
    reported but do not mask the signal exit code.
    """
    def handler(signum, frame):
        name = signal.Signals(signum).name
        print(f"{name}: draining ingest and flushing checkpoints before "
              "exit", file=sys.stderr)
        try:
            report = runtime.stop(drain=True, raise_on_failure=False)
            health = runtime.health()
            for tenant_id, rep in report.items():
                if rep.get("state") == "failed" or rep.get(
                        "unaccounted_edges"):
                    err = health.get(tenant_id, {}).get("error")
                    print(f"worker {tenant_id}: state={rep.get('state')} "
                          f"unaccounted={rep.get('unaccounted_edges')} "
                          f"error={err}", file=sys.stderr)
        finally:
            sys.exit(128 + signum)

    signal.signal(signal.SIGTERM, handler)
    signal.signal(signal.SIGINT, handler)


def run_load(args, engine, snapshot_fn, requests, *, n_nodes: int) -> tuple:
    """Measurement phase: in-process open loop, or — with ``--serve`` — a
    TCP query server loaded over ``--connections`` real client connections.
    Returns ``(report, net_extras)``; ``report`` quacks the same either way
    (n_requests / achieved_qps / p50_ms / p99_ms)."""
    if not args.serve:
        loadgen = OpenLoopLoadGen(target_qps=args.qps,
                                  batch_max=args.batch_max)
        return loadgen.run(engine, snapshot_fn, requests), {}

    from repro.net import wire
    from repro.net.query_server import QueryServer
    from repro.serving.loadgen import NetLoadGen

    host, port = wire.parse_hostport(args.serve)
    try:
        server = QueryServer(
            engine, snapshot_fn, host=host, port=port,
            max_inflight=args.max_inflight, batch_max=args.batch_max,
            tenant_qps=args.tenant_qps,
            auth_token=args.auth_token or None,
            info={"n_nodes": n_nodes, "kind": args.sketch,
                  "dataset": args.dataset}).start()
    except ValueError as exc:  # non-loopback --serve without a token
        raise SystemExit(str(exc)) from exc
    print(json.dumps({"serving":
                      f"{server.address[0]}:{server.address[1]}"}),
          file=sys.stderr, flush=True)
    try:
        if args.n_requests <= 0:
            # standing front-end: serve remote clients until the graceful
            # drain handler (SIGTERM/SIGINT) exits the process
            while True:
                time.sleep(3600)
        gen = NetLoadGen(target_qps=args.qps, connections=args.connections,
                         batch_max=args.batch_max,
                         auth_token=args.auth_token or None)
        report = gen.run(server.address, requests)
        stats = server.stats()
        return report, {
            "serve": f"{server.address[0]}:{server.address[1]}",
            "connections": args.connections,
            "shed": report.shed,
            "shed_rate": round(report.shed_rate, 4),
            "aborted": report.aborted,
            "mean_retry_after_ms": round(report.mean_retry_after_ms, 3),
            "answer_epoch": report.last_epoch,
            "server_stats": stats,
        }
    finally:
        server.stop()


def cooperative_serve(args, tenant, engine, requests) -> tuple:
    """PR 1 behaviour: ingest interleaves with query batches, one thread."""
    ingested = [0]

    def live_ingest() -> None:
        stepped = tenant.step(1)
        ingested[0] += stepped
        # key off this call's progress, not the cumulative count: once the
        # stream drains, a frozen total would either publish after every
        # served batch (thrashing the closure cache) or never again
        if stepped and ingested[0] % args.publish_every == 0:
            tenant.publish()

    loadgen = OpenLoopLoadGen(target_qps=args.qps, batch_max=args.batch_max)
    report = loadgen.run(engine, lambda: tenant.snapshot, requests,
                         between_batches=live_ingest)
    # drain whatever stream remains so the run is a full ingest too
    while tenant.step(16):
        pass
    final = tenant.publish()
    return report, final, {"ingest_mode": "cooperative"}


def _backend_arg(spec: str, publish_mode: str):
    """Backend arg for ``Runtime``, honouring ``--publish-mode``.  Only the
    remote backends publish over a transport; ``thread`` has no
    ``publish_mode`` attribute and ignores the flag."""
    if publish_mode == "delta":
        return spec  # the default everywhere; spec strings stay lazy
    from repro.runtime.backend import resolve_backend

    backend = resolve_backend(spec)
    if hasattr(backend, "publish_mode"):
        backend.publish_mode = publish_mode
    return backend


def background_serve(args, tenant, engine, requests) -> tuple:
    """Queries (main thread) truly concurrent with a runtime ingest worker."""
    from repro.runtime import Runtime

    runtime = Runtime(
        queue_capacity=args.queue_capacity,
        backpressure=args.backpressure,
        publish_policy=args.publish_policy or f"every:{args.publish_every}",
        checkpoint_dir=args.checkpoint_dir or None,
        checkpoint_every=args.checkpoint_every,
        spill_dir=args.spill_dir or None,
        dedup=args.ingest_dedup,
        backend=_backend_arg(args.runtime_backend, args.publish_mode),
    )
    runtime.attach(tenant, restore=args.restore)
    install_graceful_drain(runtime)
    runtime.start(pumps=False)
    runtime.wait_ready()  # process children build their tenants first
    runtime.start_pumps()
    report, net_extras = run_load(args, engine, lambda: tenant.snapshot,
                                  requests,
                                  n_nodes=tenant.stream.spec.n_nodes)
    mid_metrics = runtime.metrics()[tenant.key.tenant_id]
    runtime.join_pumps()  # finish offering the stream, then drain
    final_report = runtime.stop(drain=True)
    tr = final_report[tenant.key.tenant_id]
    cons = conservation_verdict(tr["published_edges"], tr["dropped_edges"],
                                tenant.stream.spec.n_edges,
                                tr["unaccounted_edges"])
    extras = {
        "ingest_mode": "background",
        "runtime_backend": args.runtime_backend,
        "worker_placement": runtime.backend.placement,
        "backpressure": args.backpressure,
        "publish_policy": args.publish_policy or f"every:{args.publish_every}",
        "ingest_edges_per_s": mid_metrics["edges_per_s_ewma"],
        "publishes": tr["publishes"],
        "mean_publish_latency_ms": tr["mean_publish_latency_ms"],
        "max_queue_depth": tr["max_queue_depth"],
        "dropped_edges": tr["dropped_edges"],
        "overflow_edges": tr["overflow_edges"],
        "spilled_batches": tr["spilled_batches"],
        "unaccounted_edges": tr["unaccounted_edges"],
        "published_edges": cons["published_edges"],
        "stream_total_edges": cons["stream_total_edges"],
        "conservation_ok": cons["conservation_ok"],
        "checkpoints": tr["checkpoints"],
        "worker_state": tr["state"],
        **net_extras,
    }
    return report, tenant.snapshot, extras


def sharded_main(args) -> None:
    """Sharded serving: K hash-band shards, one runtime worker per shard,
    scatter/gather queries (DESIGN.md §Sharding)."""
    from repro.runtime import Runtime
    from repro.serving import (QueryEngine as _QE, ShardedQueryEngine,
                               attach_shards, sharded_conservation)

    registry = SketchRegistry(depth=args.depth, scale=args.scale,
                              partitioner=args.partitioner,
                              sketch_backend=args.sketch_backend or None)
    tenant = registry.open_sharded(args.dataset, args.sketch, args.budget_kb,
                                   seed=args.seed, n_shards=args.shards,
                                   shard_seed=args.shard_seed)
    stream = tenant.stream
    n_nodes = stream.spec.n_nodes
    print(f"sharded tenant {tenant.key.tenant_id} x{args.shards}: stream "
          f"{stream.num_batches} batches, universe {n_nodes}",
          file=sys.stderr)

    if not args.restore:  # a restored tenant is already warm
        tenant.step(min(args.warm_batches,
                        max(1, stream.num_batches // 2)))
        snap = tenant.publish()
        print(f"warm: epochs {snap.epochs}, {snap.n_edges} edges",
              file=sys.stderr)

    mix = build_mix(args)
    requests = synth_requests(
        args.n_requests, mix, n_nodes=n_nodes, seed=args.seed + 7,
        heavy_universe=min(n_nodes, 1 << 14), heavy_threshold=100.0)
    engine = ShardedQueryEngine(_QE())
    warm = synth_requests(args.batch_max, mix, n_nodes=n_nodes, seed=99,
                          heavy_universe=min(n_nodes, 1 << 14),
                          heavy_threshold=100.0)
    warm_bucket_ladder(engine, tenant.snapshot, warm)

    runtime = Runtime(
        queue_capacity=args.queue_capacity,
        backpressure=args.backpressure,
        publish_policy=args.publish_policy or f"every:{args.publish_every}",
        checkpoint_dir=args.checkpoint_dir or None,
        checkpoint_every=args.checkpoint_every,
        spill_dir=args.spill_dir or None,
        # under backlog, fold sub-batches back to full-batch dispatches so
        # K small shards don't pay K-fold fixed dispatch cost
        coalesce_batches=max(4, args.shards),
        coalesce_target=stream.batch_size,
        dedup=args.ingest_dedup,
        backend=_backend_arg(args.runtime_backend, args.publish_mode),
    )
    handles = attach_shards(runtime, tenant, restore=args.restore)
    install_graceful_drain(runtime)
    runtime.start(pumps=False)
    runtime.wait_ready()  # process children build their tenants first
    runtime.start_pumps()
    report, net_extras = run_load(args, engine, lambda: tenant.snapshot,
                                  requests, n_nodes=n_nodes)
    mid = runtime.metrics()
    ingest_eps = sum(m["edges_per_s_ewma"] for m in mid.values())
    runtime.join_pumps()
    runtime.stop(drain=True)
    cons = sharded_conservation(handles, stream.spec.n_edges)

    summary = {
        "driver": "query_serve",
        "dataset": args.dataset,
        "sketch": args.sketch,
        "sketch_backend": registry.sketch_backend,
        "budget_kb": args.budget_kb,
        "ingest_mode": "sharded-background",
        "runtime_backend": args.runtime_backend,
        "worker_placement": runtime.backend.placement,
        "n_shards": args.shards,
        "achieved_qps": round(report.achieved_qps, 1),
        "offered_qps": args.qps,
        "p50_ms": round(report.p50_ms, 3),
        "p90_ms": round(report.p90_ms, 3),
        "p99_ms": round(report.p99_ms, 3),
        "p999_ms": round(report.p999_ms, 3),
        "latency_hist": report.latency_hist,
        "n_requests": report.n_requests,
        "final_epochs": list(tenant.epochs),
        "total_edges": tenant.snapshot.n_edges,
        "ingest_edges_per_s": round(ingest_eps, 1),
        "per_shard_published": cons["per_shard_published"],
        "dropped_edges": cons["dropped_edges"],
        "stream_total_edges": cons["stream_total_edges"],
        "conservation_ok": cons["conservation_ok"],
        **net_extras,
        **{f"engine_{k}": v for k, v in engine.stats.items()},
        "device": device_info(),
    }
    print(json.dumps(summary))
    if not cons["conservation_ok"]:
        sys.exit(1)


def main() -> None:
    init_compile_cache()
    args = parse_args()
    if args.no_donate:
        # must land before any SnapshotBuffer is built (tenant open);
        # runtime/backend.py forwards it to spawned/remote workers too
        os.environ["REPRO_DONATE"] = "0"
    dumper = None
    if args.metrics_json:
        from repro.obs import MetricsJsonDumper

        dumper = MetricsJsonDumper(args.metrics_json,
                                   interval_s=args.metrics_interval_s).start()
    try:
        _run(args)
    finally:
        if dumper is not None:
            dumper.stop()
        if args.span_log:
            from repro.obs import get_trace_log

            n = get_trace_log().dump_jsonl(args.span_log)
            print(f"span log: {n} events -> {args.span_log}",
                  file=sys.stderr)


def _run(args) -> None:
    if args.shards > 1:
        sharded_main(args)
        return
    summary, *_ = serve_tenant(args)
    print(json.dumps(summary))
    # a background run that lost edges exits nonzero
    if summary.get("unaccounted_edges"):
        sys.exit(1)


def serve_tenant(args) -> tuple:
    """One tenant: warm ingest, then serve ``--n-requests`` mixed queries
    while ingest continues (cooperatively or in the background runtime).

    Returns ``(summary, tenant, engine, requests)`` so a caller can gate the
    final snapshot against direct answers.
    """
    registry = SketchRegistry(depth=args.depth, scale=args.scale,
                              partitioner=args.partitioner,
                              sketch_backend=args.sketch_backend or None)
    tenant = registry.open(args.dataset, args.sketch, args.budget_kb,
                           seed=args.seed)
    n_nodes = tenant.stream.spec.n_nodes
    print(f"tenant {tenant.key.tenant_id}: stream "
          f"{tenant.stream.num_batches} batches, universe {n_nodes}",
          file=sys.stderr)

    t0 = time.time()
    if not args.restore:  # a restored tenant is already warm
        tenant.step(min(args.warm_batches,
                        max(1, tenant.stream.num_batches // 2)))
        snap = tenant.publish()
        print(f"warm: epoch {snap.epoch}, {snap.n_edges} edges in "
              f"{time.time()-t0:.2f}s", file=sys.stderr)

    mix = build_mix(args)
    requests = synth_requests(
        args.n_requests, mix, n_nodes=n_nodes, seed=args.seed + 7,
        heavy_universe=min(n_nodes, 1 << 14), heavy_threshold=100.0)

    engine = QueryEngine()
    warm = synth_requests(args.batch_max, mix, n_nodes=n_nodes, seed=99,
                          heavy_universe=min(n_nodes, 1 << 14),
                          heavy_threshold=100.0)
    warm_bucket_ladder(engine, tenant.snapshot, warm)

    serve = background_serve if args.background_ingest else cooperative_serve
    report, final, extras = serve(args, tenant, engine, requests)

    summary = {
        "driver": "query_serve",
        "dataset": args.dataset,
        "sketch": args.sketch,
        "sketch_backend": registry.sketch_backend,
        "budget_kb": args.budget_kb,
        "achieved_qps": round(report.achieved_qps, 1),
        "offered_qps": args.qps,
        "p50_ms": round(report.p50_ms, 3),
        "p90_ms": round(report.p90_ms, 3),
        "p99_ms": round(report.p99_ms, 3),
        "p999_ms": round(report.p999_ms, 3),
        "latency_hist": report.latency_hist,
        "n_requests": report.n_requests,
        "final_epoch": final.epoch,
        "total_edges": final.n_edges,
        **extras,
        **{f"engine_{k}": v for k, v in engine.stats.items()},
        "device": device_info(),
    }
    return summary, tenant, engine, requests


if __name__ == "__main__":
    main()
