"""Production streaming-ingest driver: the paper's workload as a service.

Runs the full pipeline: stream -> reservoir sample -> partition -> batched
ingest (optionally data-parallel across local devices) with periodic
checkpointing and crash-safe resume. This is the end-to-end driver for the
paper's own system (examples/quickstart.py is the 60-second version).

  python -m repro.launch.stream_ingest --dataset cit-HepPh --budget-kb 512 \
      --sketch kmatrix --steps-per-ckpt 16 --ckpt-dir /tmp/kmatrix_ckpt \
      [--resume] [--scale 0.25]

Worker-host mode (DESIGN.md §Net): ``--listen HOST:PORT`` turns this
process into a standing socket-ingest worker host — it serves ingest
worker sessions for any parent running a ``socket``-backend Runtime
pointed at this address (``--runtime-backend socket:HOST:PORT`` here or
in query_serve / serve_bench).  All other pipeline flags are ignored in
this mode: the tenant spec arrives over the wire in the hello frame.
"""
from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.checkpoint import store
from repro.core import vertex_stats_from_sample
from repro.core.metrics import (
    average_relative_error,
    exact_edge_frequencies,
    lookup_exact,
)
from repro.launch.host import device_info, init_compile_cache
from repro.serving.registry import SKETCHES, build_sketch
from repro.streams import make_stream, sample_stream


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


def runtime_main(args) -> None:
    """Paper pipeline driven through the background ingest runtime.

    Same stream -> sample -> partition -> ingest -> ARE pipeline, but the
    ingest loop is a ``repro.runtime`` worker on the chosen execution
    backend (``--runtime-backend thread|process``) behind a pump + bounded
    queue, with conservation verified after the drain — the process
    backend's write path runs in a spawn child owning the sketch, while
    this process keeps the published snapshot for evaluation.
    """
    from repro.runtime import Runtime
    from repro.serving import SketchRegistry

    registry = SketchRegistry(depth=args.depth, batch_size=args.batch_size,
                              sample_size=args.sample_size,
                              scale=args.scale,
                              partitioner=args.partitioner,
                              sketch_backend=args.sketch_backend or None)
    tenant = registry.open(args.dataset, args.sketch, args.budget_kb,
                           seed=args.seed)
    stream = tenant.stream
    print(f"stream: {stream.spec.name} nodes={stream.spec.n_nodes} "
          f"edges={stream.spec.n_edges} batches={stream.num_batches} "
          f"[runtime backend: {args.runtime_backend}]")
    runtime = Runtime(publish_policy="drain:0", reservoir_k=0,
                      checkpoint_dir=args.ckpt_dir or None,
                      checkpoint_every=args.steps_per_ckpt,
                      dedup=args.ingest_dedup,
                      backend=_backend_arg(args.runtime_backend,
                                           args.publish_mode))
    restore = bool(args.resume and args.ckpt_dir)
    try:
        handle = runtime.attach(tenant, restore=restore)
    except FileNotFoundError:
        print("no checkpoint found; starting fresh")
        handle = runtime.attach(tenant, restore=False)
    if restore and tenant.offset:
        print(f"resumed from batch {tenant.offset}")
    t0 = time.time()
    runtime.start(pumps=False)
    runtime.wait_ready()
    runtime.start_pumps()
    runtime.join_pumps()
    report = runtime.stop(drain=True)[tenant.key.tenant_id]
    dt = time.time() - t0
    n_edges = report["ingested_edges"]
    print(f"ingest: {n_edges} edges in {dt:.2f}s "
          f"({n_edges/max(dt,1e-9)/1e6:.2f} M edges/s) "
          f"unaccounted={report['unaccounted_edges']}")
    if report["unaccounted_edges"]:
        raise SystemExit("edge conservation failed after drain")

    are = evaluate_are(args, stream, tenant.snapshot.sketch, tenant.mod)
    print(json.dumps({"sketch": args.sketch, "dataset": args.dataset,
                      "budget_kb": args.budget_kb,
                      "runtime_backend": args.runtime_backend,
                      "worker_placement": runtime.backend.placement,
                      "ARE": round(are, 4), "device": device_info()}))


def listen_main(args) -> None:
    """Standing worker host: serve socket ingest sessions until signalled
    (or until ``--max-sessions`` sessions completed, for scripted runs)."""
    import signal as signal_mod

    from repro.net import wire
    from repro.net.ingest_server import WorkerServer

    host, port = wire.parse_hostport(args.listen)
    try:
        server = WorkerServer(host, port,
                              auth_token=args.auth_token or None)
    except ValueError as exc:  # non-loopback bind without a token
        raise SystemExit(str(exc)) from exc
    print(json.dumps({"listening": f"{server.address[0]}:{server.address[1]}",
                      "max_sessions": args.max_sessions or None}), flush=True)

    def _stop(signum, frame):
        server.stop()

    signal_mod.signal(signal_mod.SIGTERM, _stop)
    signal_mod.signal(signal_mod.SIGINT, _stop)
    server.serve_forever(
        max_sessions=args.max_sessions or None,
        idle_timeout_s=args.idle_timeout_s or None)
    print(json.dumps({"sessions_served": server.sessions_served,
                      "results": server.session_results}), flush=True)
    if any(str(r).startswith("aborted") or r == "failed"
           for r in server.session_results):
        raise SystemExit(1)


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="cit-HepPh")
    ap.add_argument("--sketch", default="kmatrix", choices=sorted(SKETCHES))
    ap.add_argument("--budget-kb", type=int, default=512)
    ap.add_argument("--depth", type=int, default=7)
    ap.add_argument("--batch-size", type=int, default=8192)
    ap.add_argument("--sample-size", type=int, default=30_000)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--scale", type=float, default=1.0)
    ap.add_argument("--partitioner", default="banded",
                    choices=["banded", "greedy"])
    ap.add_argument("--sketch-backend", default="",
                    choices=["", "flat", "pallas"],
                    help="kmatrix physical layout (default: "
                         "$REPRO_SKETCH_BACKEND, else pallas on TPU / flat "
                         "elsewhere); checkpoints are layout-specific but "
                         "convertible via core.kmatrix_accel relayout")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--steps-per-ckpt", type=int, default=16)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--eval-queries", type=int, default=10_000)
    ap.add_argument("--ingest-dedup", action="store_true",
                    help="runtime backends only: pre-aggregate duplicate "
                         "(src, dst) rows on the host before each coalesced "
                         "ingest dispatch (bit-exact — counters are linear)")
    ap.add_argument("--no-donate", action="store_true",
                    help="disable jit buffer donation in the ingest path "
                         "(sets REPRO_DONATE=0 for this process and its "
                         "workers; A/B and debugging aid)")
    ap.add_argument("--runtime-backend", default="inline",
                    help="inline: this loop ingests directly (default). "
                         "thread/process/socket[:HOST:PORT,...]: drive "
                         "ingest through the repro.runtime worker runtime "
                         "on that execution backend (pump + bounded queue "
                         "+ conservation accounting; checkpoints use the "
                         "runtime's worker-state schema under a per-tenant "
                         "subdir). socket with no address self-hosts a "
                         "loopback worker; with addresses it dials "
                         "--listen worker hosts")
    ap.add_argument("--publish-mode", default="delta",
                    choices=["delta", "full"],
                    help="remote-backend snapshot publication: 'delta' "
                         "(default) ships only the per-epoch sketch delta, "
                         "sparse-encoded; 'full' ships whole fronts every "
                         "epoch (pre-v3 behaviour, kept for A/B benching)")
    ap.add_argument("--listen", default="", metavar="HOST:PORT",
                    help="worker-host mode: serve socket ingest worker "
                         "sessions at this address instead of running a "
                         "pipeline (DESIGN.md §Net)")
    ap.add_argument("--max-sessions", type=int, default=0,
                    help="with --listen: exit after N completed sessions "
                         "(0 = serve until signalled)")
    ap.add_argument("--idle-timeout-s", type=float, default=0.0,
                    help="with --listen: exit after this long with no live "
                         "session (0 = wait forever); keeps scripted runs "
                         "from wedging on a lost parent")
    ap.add_argument("--auth-token", default="",
                    help="shared connection token (default: "
                         "$KMATRIX_NET_TOKEN); REQUIRED to --listen on a "
                         "non-loopback address — parents present it via "
                         "the same flag/env on their socket backend")
    ap.add_argument("--metrics-json", default="", metavar="PATH",
                    help="periodically dump the merged metrics hub to PATH "
                         "as JSON (atomic replace; same payload as the "
                         "'metrics' wire frame); works in every mode, "
                         "including --listen worker hosts")
    ap.add_argument("--metrics-interval-s", type=float, default=1.0,
                    help="with --metrics-json: seconds between dumps")
    args = ap.parse_args(argv)
    valid = ("inline", "thread", "process", "socket")
    if args.runtime_backend not in valid \
            and not args.runtime_backend.startswith("socket:"):
        ap.error(f"--runtime-backend must be one of {valid} or "
                 f"socket:HOST:PORT[,...], got {args.runtime_backend!r}")
    if args.ingest_dedup and args.runtime_backend == "inline" \
            and not args.listen:
        ap.error("--ingest-dedup requires a runtime backend "
                 "(--runtime-backend thread/process/socket)")
    return args


def main() -> None:
    init_compile_cache()
    args = parse_args()
    if args.no_donate:
        # must land before any SnapshotBuffer is built; the runtime
        # backends forward it to spawned/remote workers via the child spec
        os.environ["REPRO_DONATE"] = "0"
    dumper = None
    if args.metrics_json:
        from repro.obs import MetricsJsonDumper

        dumper = MetricsJsonDumper(args.metrics_json,
                                   interval_s=args.metrics_interval_s).start()
    try:
        if args.listen:
            listen_main(args)
        elif args.runtime_backend != "inline":
            runtime_main(args)
        else:
            inline_main(args)
    finally:
        if dumper is not None:
            dumper.stop()


def build_inline(args):
    """Stream -> bootstrap sample -> partition plan -> empty sketch.

    Returns ``(stream, sketch, module)``.
    """
    stream = make_stream(args.dataset, batch_size=args.batch_size,
                         seed=args.seed, scale=args.scale)
    print(f"stream: {stream.spec.name} nodes={stream.spec.n_nodes} "
          f"edges={stream.spec.n_edges} batches={stream.num_batches}")

    # Paper §V-A: 30k-edge reservoir sample bootstraps the partitioner.
    t0 = time.time()
    ssrc, sdst, sw = sample_stream(stream, args.sample_size, seed=args.seed + 1)
    stats = vertex_stats_from_sample(ssrc, sdst, sw)
    sk, mod = build_sketch(args.sketch, args.budget_kb * 1024, stats,
                           args.depth, args.seed, args.partitioner,
                           backend=args.sketch_backend or None)
    print(f"init: {args.sketch} [{type(sk).__name__}] "
          f"counters={sk.num_counters} "
          f"({time.time()-t0:.2f}s init incl. sampling)")
    return stream, sk, mod


def run_inline_ingest(args, stream, sk, mod):
    """Fold the whole stream into ``sk`` with one jitted step per batch,
    checkpointing every ``--steps-per-ckpt`` batches when ``--ckpt-dir`` is
    set.  Returns ``(sketch, jitted_step)``."""
    offset = 0
    if args.resume and args.ckpt_dir:
        try:
            sk, meta = store.restore(args.ckpt_dir, sk)
            offset = meta["extra"]["stream_offset"]
            print(f"resumed from batch {offset}")
        except FileNotFoundError:
            print("no checkpoint found; starting fresh")

    ingest = jax.jit(mod.ingest)
    t0 = time.time()
    n_edges = 0
    for i, batch in stream.iter_from(offset):
        sk = ingest(sk, batch)
        n_edges += int(np.asarray(batch.weight > 0).sum())
        if args.ckpt_dir and (i + 1) % args.steps_per_ckpt == 0:
            jax.block_until_ready(sk)
            store.save(args.ckpt_dir, i + 1, sk,
                       extra={"stream_offset": i + 1, "seed": args.seed})
    jax.block_until_ready(sk)
    dt = time.time() - t0
    print(f"ingest: {n_edges} edges in {dt:.2f}s "
          f"({n_edges/max(dt,1e-9)/1e6:.2f} M edges/s)")
    return sk, ingest


def evaluate_are(args, stream, sk, mod) -> float:
    """Average relative error of ``--eval-queries`` sampled edge queries
    against exact stream frequencies (paper Fig. 7 protocol)."""
    src, dst, w = stream.all_edges_numpy()
    fmap = exact_edge_frequencies(src, dst, w)
    qs, qd, _ = sample_stream(stream, args.eval_queries, seed=99)
    true = lookup_exact(fmap, qs, qd)
    est = np.asarray(mod.edge_freq(sk, jnp.asarray(qs), jnp.asarray(qd)))
    return float(average_relative_error(jnp.asarray(est), jnp.asarray(true)))


def inline_main(args) -> None:
    """The original single-loop pipeline: jit ingest in this thread."""
    stream, sk, mod = build_inline(args)
    sk, _ = run_inline_ingest(args, stream, sk, mod)
    are = evaluate_are(args, stream, sk, mod)
    print(json.dumps({"sketch": args.sketch, "dataset": args.dataset,
                      "budget_kb": args.budget_kb, "ARE": round(are, 4),
                      "device": device_info()}))


if __name__ == "__main__":
    main()
