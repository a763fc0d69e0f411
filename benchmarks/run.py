"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (plus figure tables to stderr).

  fig6_build_time   paper Fig. 6  — ingest throughput per sketch x dataset
  fig7_are          paper Fig. 7  — ARE vs memory budget (Type II sketches)
  fig8_neq          paper Fig. 8  — number/percent of effective queries
  partitioner_ablation — beyond-paper: greedy (Eq.8) vs banded sqrt-G
  kernel_micro      — Pallas kernels (interpret) vs pure-jnp reference ops
  ingest            — flat-scatter vs width-class accel sketch backend
                      edges/s (emits BENCH_ingest.json, bit-exactness gated)
                      + dispatch-capacity policy: plan-derived vs 2B/P
                      overflow on a skewed stream (strict-improvement gated)
  serve_sharded     — sharded serving at K=1/2/4: per-shard runtime ingest
                      + scatter/gather queries (emits BENCH_sharded.json,
                      conservation + merged-exactness gated)
  serve_process     — thread vs process runtime backends at K=1/2/4
                      (emits BENCH_process.json; same sharded hard gates,
                      process K4/K1 scaling recorded vs cpu_count)
  serve_net         — network transport tier (emits BENCH_net.json):
                      socket vs process ingest edges/s under the same
                      sharded hard gates, TCP query front-end QPS/p50/p99
                      at 1/2/4 connections, and an overload cell gated on
                      nonzero accounted shed with bounded accepted-p99
  obs               — telemetry overhead (emits BENCH_obs.json): metrics-on
                      vs metrics-off ingest edges/s + query p99, gated on
                      metrics-on staying within 5% of metrics-off

Usage: PYTHONPATH=src python -m benchmarks.run [--quick] [--only fig7_are]
"""
from __future__ import annotations

import argparse
import os
import sys
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import (
    CountMin,
    GSketch,
    KMatrix,
    KMatrixAccel,
    MatrixSketch,
    vertex_stats_from_sample,
)
from repro.core import countmin, gsketch, kmatrix, kmatrix_accel, matrix_sketch
from repro.core.metrics import (
    average_relative_error,
    effective_queries,
    exact_edge_frequencies,
    lookup_exact,
    percent_effective_queries,
)
from repro.streams import make_stream, sample_stream

DATASETS = ["unicorn-wget", "email-EuAll", "cit-HepPh"]


def _emit(name: str, us: float, derived: str) -> None:
    print(f"{name},{us:.3f},{derived}")


def _log(msg: str) -> None:
    print(msg, file=sys.stderr)


def _build_all(budget: int, depth: int, stats, seed=3):
    return {
        "countmin": (CountMin.create(bytes_budget=budget, depth=depth, seed=seed),
                     countmin),
        "gsketch": (GSketch.create(bytes_budget=budget, stats=stats, depth=depth,
                                   seed=seed, min_width=32), gsketch),
        "tcm": (MatrixSketch.create(bytes_budget=budget, depth=depth, seed=seed,
                                    kind="tcm"), matrix_sketch),
        "gmatrix": (MatrixSketch.create(bytes_budget=budget, depth=depth,
                                        seed=seed + 1, kind="gmatrix"),
                    matrix_sketch),
        "kmatrix": (KMatrix.create(bytes_budget=budget, stats=stats, depth=depth,
                                   seed=seed), kmatrix),
        # same sketch, width-class layout: ingest goes through the Pallas MXU
        # kernel (interpret mode off-TPU, so its fig6 column measures the
        # correctness path there, not kernel speed)
        "kmatrix_accel": (KMatrixAccel.create(bytes_budget=budget, stats=stats,
                                              depth=depth, seed=seed),
                          kmatrix_accel),
    }


def _ingest_all(stream, sk, mod):
    ing = jax.jit(mod.ingest)
    t0 = time.time()
    for b in stream:
        sk = ing(sk, b)
    jax.block_until_ready(jax.tree_util.tree_leaves(sk)[0])
    return sk, time.time() - t0


def fig6_build_time(scale: float) -> None:
    """Paper Fig. 6: time to add the entire dataset (1 MB sketches, d=7)."""
    _log("\n== fig6_build_time (1MB, d=7) ==")
    _log(f"{'dataset':14s} {'sketch':13s} {'edges/s':>12s} {'us/edge':>9s}")
    for ds in DATASETS:
        stream = make_stream(ds, batch_size=8192, seed=1, scale=scale)
        ssrc, sdst, sw = sample_stream(stream, int(30_000 * scale) or 1000, seed=7)
        stats = vertex_stats_from_sample(ssrc, sdst, sw)
        for name, (sk, mod) in _build_all(1 << 20, 7, stats).items():
            sk, dt = _ingest_all(stream, sk, mod)
            n = stream.spec.n_edges
            _log(f"{ds:14s} {name:13s} {n/dt:12,.0f} {dt/n*1e6:9.3f}")
            _emit(f"fig6/{ds}/{name}", dt / n * 1e6, f"edges_per_s={n/dt:.0f}")


def _eval_accuracy(stream, states, mods, n_queries, g0_list=(1.0, 10.0)):
    src, dst, w = stream.all_edges_numpy()
    fmap = exact_edge_frequencies(src, dst, w)
    qs, qd, _ = sample_stream(stream, n_queries, seed=99)
    true = jnp.asarray(lookup_exact(fmap, qs, qd))
    out = {}
    for name, sk in states.items():
        est = mods[name].edge_freq(sk, jnp.asarray(qs), jnp.asarray(qd))
        are = float(average_relative_error(est, true))
        neq = {g0: int(effective_queries(est, true, g0)) for g0 in g0_list}
        peq = {g0: float(percent_effective_queries(est, true, g0))
               for g0 in g0_list}
        out[name] = {"are": are, "neq": neq, "peq": peq}
    return out


def fig7_fig8_accuracy(scale: float, quick: bool) -> None:
    """Paper Fig. 7 (ARE) + Fig. 8 (NEQ): accuracy vs memory budget."""
    budgets = [200, 512] if quick else [200, 300, 400, 512]
    n_q = 2_000 if quick else 10_000
    depth = 7
    _log("\n== fig7_are / fig8_neq ==")
    _log(f"{'dataset':14s} {'kb':>4s} {'sketch':9s} {'ARE':>9s} "
         f"{'NEQ@1':>7s} {'PEQ@10':>8s}")
    for ds in DATASETS:
        stream = make_stream(ds, batch_size=8192, seed=1, scale=scale)
        ssrc, sdst, sw = sample_stream(stream, int(30_000 * scale) or 1000, seed=7)
        stats = vertex_stats_from_sample(ssrc, sdst, sw)
        for kb in budgets:
            sketches = _build_all(kb * 1024, depth, stats)
            # paper compares Type II only in Figs 7-8
            type2 = {k: v for k, v in sketches.items()
                     if k in ("tcm", "gmatrix", "kmatrix")}
            states, mods = {}, {}
            for name, (sk, mod) in type2.items():
                sk, dt = _ingest_all(stream, sk, mod)
                states[name], mods[name] = sk, mod
            acc = _eval_accuracy(stream, states, mods, n_q)
            for name, a in acc.items():
                _log(f"{ds:14s} {kb:4d} {name:9s} {a['are']:9.2f} "
                     f"{a['neq'][1.0]:7d} {a['peq'][10.0]:7.1f}%")
                _emit(f"fig7/{ds}/{kb}kb/{name}", 0.0, f"ARE={a['are']:.4f}")
                _emit(f"fig8/{ds}/{kb}kb/{name}", 0.0,
                      f"NEQ_g1={a['neq'][1.0]};PEQ_g10={a['peq'][10.0]:.2f}")


def partitioner_ablation(scale: float) -> None:
    """Beyond-paper: Eq.8 greedy vs banded sqrt-G vs two-term-model auto."""
    _log("\n== partitioner_ablation (256KB, d=5) ==")
    for ds in DATASETS:
        stream = make_stream(ds, batch_size=8192, seed=1, scale=scale)
        ssrc, sdst, sw = sample_stream(stream, int(30_000 * scale) or 1000, seed=7)
        stats = vertex_stats_from_sample(ssrc, sdst, sw)
        states, mods = {}, {}
        for mode in ["greedy", "banded", "auto"]:
            sk = KMatrix.create(bytes_budget=256 * 1024, stats=stats, depth=5,
                                seed=3, partitioner=mode)
            sk, dt = _ingest_all(stream, sk, kmatrix)
            states[mode], mods[mode] = sk, kmatrix
        acc = _eval_accuracy(stream, states, mods, 4000)
        for mode, a in acc.items():
            n_p = states[mode].route.n_partitions
            _log(f"{ds:14s} {mode:7s} ARE={a['are']:.3f} partitions={n_p}")
            _emit(f"ablate_partitioner/{ds}/{mode}", 0.0,
                  f"ARE={a['are']:.4f};partitions={n_p}")


def kernel_micro(quick: bool) -> None:
    """Pallas kernels (interpret mode off-TPU) vs jnp reference."""
    from repro.kernels import matrix_ingest, matrix_lookup
    from repro.kernels import ref as kref
    from repro.kernels.ops import interpret_mode

    interpret = interpret_mode()
    _log(f"\n== kernel_micro (interpret={interpret}) ==")
    d, p, w, c = 5, 1, 256, 4096
    rng = np.random.default_rng(0)
    pool = jnp.zeros((d, p, w, w), jnp.int32)
    hi = jnp.asarray(rng.integers(0, w, (d, p, c)), jnp.int32)
    hj = jnp.asarray(rng.integers(0, w, (d, p, c)), jnp.int32)
    wt = jnp.ones((p, c), jnp.int32)

    for name, fn in [
        ("pallas_matrix_ingest", lambda: matrix_ingest(
            pool, hi, hj, wt, block_b=256, interpret=interpret)),
        ("jnp_matrix_ingest_ref", lambda: kref.matrix_ingest_ref(pool, hi, hj, wt)),
        ("pallas_matrix_lookup", lambda: matrix_lookup(
            pool, hi, hj, block_q=256, interpret=interpret)),
        ("jnp_matrix_lookup_ref", lambda: kref.matrix_lookup_ref(pool, hi, hj)),
    ]:
        fn()  # compile
        n = 3 if quick else 10
        t0 = time.time()
        for _ in range(n):
            jax.block_until_ready(fn())
        us = (time.time() - t0) / n * 1e6
        _log(f"{name:24s} {us:12,.0f} us/call")
        _emit(f"kernel/{name}", us, f"edges={c}")


def ingest_backends(scale: float, quick: bool,
                    out_path: str = "BENCH_ingest.json") -> None:
    """flat-scatter vs width-class accel ingest throughput -> BENCH_ingest.json.

    Both backends are interpret-safe (the accel path runs the Pallas kernel
    with interpret=True off-TPU), ingest the SAME stream prefix into the
    SAME quantized layout, and must land bit-identical counters — the bench
    hard-fails otherwise, so the perf trajectory can never quietly trade
    exactness for speed.  The JSON gives fast CI a per-commit edges/s data
    point per backend, plus the donation x dedup fast-path grid
    (``_fastpath_grid``) with its own bit-exactness and 1.5x speedup
    gates.
    """
    import json as _json

    from repro.core import kmatrix_accel as kma
    from repro.kernels.ops import interpret_mode

    dataset = "cit-HepPh"
    stream = make_stream(dataset, batch_size=4096, seed=1, scale=scale)
    ssrc, sdst, sw = sample_stream(stream, int(30_000 * scale) or 1000, seed=7)
    stats = vertex_stats_from_sample(ssrc, sdst, sw)
    capacity = _capacity_policy_compare(stream, stats, quick)
    fastpath = _fastpath_grid(scale, quick)
    n_batches = min(stream.num_batches, 3 if quick else 16)
    edges = sum(int((np.asarray(stream.batch(i).weight) > 0).sum())
                for i in range(n_batches))
    accel = KMatrixAccel.create(bytes_budget=256 * 1024, stats=stats,
                                depth=5, seed=3)
    flat = kma.to_flat_layout(kma.empty_like(accel))  # bit-exact twin layout
    _log(f"\n== ingest ({dataset}, {n_batches} batches, {edges} edges, "
         f"256KB d=5, interpret={interpret_mode()}) ==")

    states, backends = {}, {}
    for name, sk, mod in [("flat", flat, kmatrix), ("pallas", accel, kma)]:
        ing = jax.jit(mod.ingest)
        warm = ing(sk, stream.batch(0))  # compile off the clock
        jax.block_until_ready(jax.tree_util.tree_leaves(warm)[0])
        t0 = time.time()
        st = sk
        for i in range(n_batches):
            st = ing(st, stream.batch(i))
        jax.block_until_ready(jax.tree_util.tree_leaves(st)[0])
        dt = time.time() - t0
        states[name] = st
        backends[name] = {"wall_s": round(dt, 4),
                          "edges_per_s": round(edges / max(dt, 1e-9), 1)}
        _log(f"{name:8s} {edges / max(dt, 1e-9):12,.0f} edges/s "
             f"({dt:.3f}s)")
        _emit(f"ingest/{name}", dt / max(edges, 1) * 1e6,
              f"edges_per_s={edges / max(dt, 1e-9):.0f}")

    from repro.serving.gates import layout_counters_equal

    relayout = kma.to_flat_layout(states["pallas"])
    bit_exact = layout_counters_equal(relayout, states["flat"])
    record = {
        "bench": "ingest",
        "dataset": dataset,
        "scale": scale,
        "n_batches": n_batches,
        "edges": edges,
        "depth": 5,
        "budget_kb": 256,
        "interpret": interpret_mode(),
        "overflow_edges": int(states["pallas"].overflow),
        "backends": backends,
        "bit_exact": bit_exact,
        "capacity_policy": capacity,
        "fastpath": fastpath,
    }
    with open(out_path, "w") as f:
        _json.dump(record, f, indent=2)
    _log(f"wrote {out_path}")
    if not bit_exact:
        raise RuntimeError(
            "ingest: accel backend counters diverged from the flat backend "
            "on the same stream — edges/s for wrong counters is meaningless")
    if not capacity["counters_equal"]:
        raise RuntimeError(
            "ingest: capacity policy changed counter state — dispatch "
            "capacity must only move edges between the MXU path and the "
            "exact scatter fallback, never change what is counted")
    if capacity["overflow_plan_capacity"] >= capacity["overflow_2bp_capacity"]:
        raise RuntimeError(
            "ingest: plan-derived dispatch capacity did not reduce the "
            "scatter-fallback volume vs the 2B/P baseline on a skewed "
            f"stream ({capacity['overflow_plan_capacity']} >= "
            f"{capacity['overflow_2bp_capacity']}) — the capacity-policy "
            "fix regressed")
    bad_cells = [k for k, c in fastpath["cells"].items()
                 if not c["bit_exact_vs_baseline"]]
    if bad_cells:
        raise RuntimeError(
            "ingest: fast-path cells diverged from the undonated/undeduped "
            f"baseline: {bad_cells} — donation is an allocation strategy "
            "and pre-aggregation rides on counter linearity; neither may "
            "change a single counter, pending total, or estimate")
    if fastpath["fastpath_speedup"] < 1.5:
        raise RuntimeError(
            "ingest: donate+dedup arm is only "
            f"{fastpath['fastpath_speedup']:.2f}x the baseline edges/s on "
            "the skewed-stream config (same box, same run) — the fast "
            "path regressed below the 1.5x acceptance floor")


def _fastpath_grid(scale: float, quick: bool) -> dict:
    """Ingest fast path A/B (ISSUE 10): donation x dedup, 4 cells.

    Skewed-stream config (email-EuAll, Zipf) where duplicate (src, dst)
    rows are plentiful: each cell drives the SAME pre-built coalesced
    groups through a ``SnapshotBuffer`` — dedup cells pre-aggregate on
    the host first (``preaggregate_edges``), donate cells run the
    donating kernels — and every cell must land counters, n_edges, AND
    estimates bit-identical to the undonated/undeduped baseline
    (counters are linear; donation is an allocation strategy).  The
    caller hard-gates ``fastpath_speedup`` (donate+dedup vs baseline
    edges/s, same box, same run) at 1.5x.
    """
    from repro.runtime.worker import preaggregate_edges
    from repro.serving.gates import layout_counters_equal
    from repro.serving.snapshot import SnapshotBuffer
    from repro.core.types import EdgeBatch

    dataset = "email-EuAll"
    fp_scale = max(scale, 0.3)  # the dedup win needs real skew volume
    group_batches = 8
    stream = make_stream(dataset, batch_size=4096, seed=5, scale=fp_scale)
    ssrc, sdst, sw = sample_stream(stream, 3000, seed=7)
    stats = vertex_stats_from_sample(ssrc, sdst, sw)
    n_groups = min(stream.num_batches // group_batches, 4 if quick else 10)
    groups, bi = [], 0
    for _ in range(n_groups):
        cols = [stream.batch_numpy(bi + k) for k in range(group_batches)]
        bi += group_batches
        groups.append(tuple(
            np.ascontiguousarray(np.concatenate([c[j] for c in cols]),
                                 np.int32) for j in range(3)))
    raw_edges = sum(int(np.count_nonzero(g[2])) for g in groups)
    unique_rows = sum(preaggregate_edges(*g)[0].shape[0] for g in groups)

    def one_pass(buf, dedup):
        for g in groups:
            if dedup:
                us, ud, uw = preaggregate_edges(*g)
                n = us.shape[0]
                pad = -(-n // 1024) * 1024  # coarse ladder: few jit shapes
                src = np.zeros(pad, np.int32)
                dst = np.zeros(pad, np.int32)
                wt = np.zeros(pad, np.int32)
                src[:n], dst[:n], wt[:n] = us, ud, uw
                buf.ingest(EdgeBatch.from_numpy(src, dst, wt),
                           count=int(np.count_nonzero(g[2])))
            else:
                buf.ingest(EdgeBatch.from_numpy(*g))
        snap = buf.publish()
        jax.block_until_ready(jax.tree_util.tree_leaves(snap.sketch)[0])
        return snap

    def fresh_buffer(donate):
        sk = KMatrix.create(bytes_budget=256 * 1024, stats=stats,
                            depth=5, seed=3)
        return SnapshotBuffer(sk, kmatrix, tenant_id="bench-fastpath",
                              donate=donate)

    probe = np.arange(256, dtype=np.int32)
    probe_dst = ((probe * 31 + 7) % stream.spec.n_nodes).astype(np.int32)
    cells, snaps = {}, {}
    for donate in (False, True):
        for dedup in (False, True):
            one_pass(fresh_buffer(donate), dedup)  # compile off the clock
            best, snap = None, None
            for _ in range(3 if quick else 5):
                buf = fresh_buffer(donate)
                t0 = time.time()
                snap = one_pass(buf, dedup)
                dt = time.time() - t0
                best = dt if best is None else min(best, dt)
            key = f"donate={int(donate)},dedup={int(dedup)}"
            snaps[key] = snap
            cells[key] = {"wall_s": round(best, 4),
                          "edges_per_s": round(raw_edges / best, 1)}
            _log(f"fastpath {key:19s} "
                 f"{raw_edges / best:12,.0f} edges/s ({best:.3f}s)")

    base_key = "donate=0,dedup=0"
    base = snaps[base_key]
    base_est = np.asarray(kmatrix.edge_freq(base.sketch, probe, probe_dst))
    for key, snap in snaps.items():
        ok = (layout_counters_equal(snap.sketch, base.sketch)
              and snap.n_edges == base.n_edges
              and np.array_equal(np.asarray(
                  kmatrix.edge_freq(snap.sketch, probe, probe_dst)),
                  base_est))
        cells[key]["bit_exact_vs_baseline"] = ok
    speedup = cells["donate=1,dedup=1"]["edges_per_s"] / \
        cells[base_key]["edges_per_s"]
    out = {
        "dataset": dataset,
        "scale": fp_scale,
        "group_batches": group_batches,
        "n_groups": n_groups,
        "raw_edges": raw_edges,
        "dedup_ratio": round(raw_edges / max(unique_rows, 1), 4),
        "cells": cells,
        "fastpath_speedup": round(speedup, 4),
    }
    _emit("ingest/fastpath", 0.0,
          f"speedup={speedup:.2f};dedup_ratio={out['dedup_ratio']:.2f}")
    return out


def _capacity_policy_compare(stream, stats, quick: bool) -> dict:
    """Dispatch-capacity policy on a skewed stream: plan-derived (the fix)
    vs the legacy uniform ``2B/P`` baseline.

    Uses the production ``banded`` partitioner (the registry default, P=17)
    where the hot band's load exceeds 2B/P by the skew factor.  Capacity is
    a dispatch concern only, so both runs must land bit-identical counters;
    the plan-derived capacity must STRICTLY cut ``overflow_edges`` (the
    scatter-fallback volume) — both enforced by the caller."""
    from repro.core import kmatrix_accel as kma
    from repro.serving.gates import layout_counters_equal

    accel = KMatrixAccel.create(bytes_budget=256 * 1024, stats=stats,
                                depth=5, seed=3, partitioner="banded")
    b = stream.batch_size
    n_parts = accel.route.n_partitions
    legacy = max(128, (2 * b) // max(n_parts, 1))
    legacy = -(-legacy // 128) * 128
    plan_cap = kma.dispatch_capacity(accel, b)
    n_batches = min(stream.num_batches, 3 if quick else 8)
    st_plan, st_legacy = accel, accel
    for i in range(n_batches):
        batch = stream.batch(i)
        st_plan = kma.ingest(st_plan, batch)  # default: plan-derived
        st_legacy = kma.ingest(st_legacy, batch, capacity=legacy)
    counters_equal = layout_counters_equal(st_plan, st_legacy)
    out = {
        "partitioner": "banded",
        "n_partitions": n_parts,
        "batch_size": b,
        "n_batches": n_batches,
        "capacity_2bp": legacy,
        "capacity_plan": plan_cap,
        "max_load_share": round(max(accel.load_shares), 4),
        "overflow_2bp_capacity": int(st_legacy.overflow),
        "overflow_plan_capacity": int(st_plan.overflow),
        "counters_equal": counters_equal,
    }
    _log(f"capacity policy (banded, P={n_parts}, B={b}): overflow "
         f"{out['overflow_2bp_capacity']} @2B/P={legacy} -> "
         f"{out['overflow_plan_capacity']} @plan={plan_cap}")
    _emit("ingest/capacity_policy", 0.0,
          f"overflow_2bp={out['overflow_2bp_capacity']};"
          f"overflow_plan={out['overflow_plan_capacity']}")
    return out


def serve_mixed(scale: float, quick: bool) -> None:
    """Beyond-paper: online serving QPS/latency (benchmarks/serve_bench.py)."""
    from benchmarks.serve_bench import run_serve_bench

    _log("\n== serve_mixed (live ingest + batched query engine) ==")
    rec = run_serve_bench(scale=scale, n_requests=1000 if quick else 4000,
                          target_qps=1000.0 if quick else 2000.0)
    if not rec["engine_matches_direct"]:
        raise RuntimeError(
            "serve_mixed: engine answers diverged from direct queries — "
            "QPS numbers for wrong answers are meaningless")
    if not rec.get("backend_parity_ok", True):
        raise RuntimeError(
            "serve_mixed: accel sketch backend diverged from the flat "
            "backend on the same stream prefix")
    _emit("serve/qps", 1e6 / max(rec["achieved_qps"], 1e-9),
          f"qps={rec['achieved_qps']};p50_ms={rec['p50_ms']};"
          f"p99_ms={rec['p99_ms']}")
    _emit("serve/closure_cache", rec["closure_build_ms"] * 1e3,
          f"hit_ms={rec['closure_cache_hit_ms']};"
          f"speedup={rec['closure_cache_speedup']}")


def serve_concurrent(scale: float, quick: bool) -> None:
    """Concurrent regime: background runtime ingest under live query load —
    ingest edges/s and query p50/p99 side by side in one record."""
    from benchmarks.serve_bench import run_serve_bench_concurrent

    _log("\n== serve_concurrent (background ingest worker + loadgen) ==")
    rec = run_serve_bench_concurrent(
        scale=scale, n_requests=1000 if quick else 4000,
        target_qps=1000.0 if quick else 2000.0)
    if not rec["engine_matches_direct"]:
        raise RuntimeError(
            "serve_concurrent: engine answers diverged from direct queries "
            "on a published epoch")
    if not rec["conservation_ok"]:
        raise RuntimeError(
            f"serve_concurrent: edge conservation failed "
            f"(unaccounted={rec['unaccounted_edges']})")
    _emit("serve/concurrent_qps", 1e6 / max(rec["achieved_qps"], 1e-9),
          f"qps={rec['achieved_qps']};p50_ms={rec['p50_ms']};"
          f"p99_ms={rec['p99_ms']};"
          f"ingest_eps={rec['ingest_edges_per_s_during_serve']}")
    _emit("serve/concurrent_ingest",
          rec["mean_publish_latency_ms"] * 1e3,
          f"epochs={rec['epochs_published']};"
          f"max_queue_depth={rec['max_queue_depth']};"
          f"dropped={rec['dropped_edges']}")


def serve_sharded(scale: float, quick: bool,
                  out_path: str = "BENCH_sharded.json") -> None:
    """Sharded serving at K=1/2/4 -> BENCH_sharded.json.

    Per K: aggregate ingest edges/s under live query load plus p50/p99, with
    BOTH sharded hard gates enforced (cross-shard conservation; merged
    shards bit-identical to a single-sketch replay).  The JSON gives fast
    CI a per-commit scaling curve for the scatter/gather serving path.
    """
    import json as _json

    from benchmarks.serve_bench import run_serve_bench_sharded

    _log("\n== serve_sharded (per-shard runtime ingest + scatter/gather) ==")
    shards: dict[str, dict] = {}
    for k in (1, 2, 4):
        rec = run_serve_bench_sharded(
            scale=scale, n_requests=600 if quick else 2000,
            target_qps=1000.0 if quick else 2000.0, n_shards=k)
        # serve_process reuses these thread rows when it runs in the same
        # sweep, instead of re-running the identical thread bench
        _SHARDED_THREAD_RECS[(scale, k)] = rec
        if not rec["conservation_ok"]:
            raise RuntimeError(
                f"serve_sharded K={k}: cross-shard conservation failed "
                f"(published {rec['published_edges']} + dropped "
                f"{rec['dropped_edges']} != stream "
                f"{rec['stream_total_edges']})")
        if rec["sharded_exact"] is False:
            raise RuntimeError(
                f"serve_sharded K={k}: merged shard sketches diverged from "
                "the single-sketch replay — the hash-band routing invariant "
                "is broken")
        if not rec["engine_matches_direct"]:
            raise RuntimeError(
                f"serve_sharded K={k}: scatter/gather engine diverged from "
                "the sharded direct oracle")
        shards[str(k)] = {
            "ingest_edges_per_s": rec["ingest_edges_per_s_dedicated"],
            "ingest_edges_per_s_during_serve":
                rec["ingest_edges_per_s_during_serve"],
            "achieved_qps": rec["achieved_qps"],
            "p50_ms": rec["p50_ms"],
            "p99_ms": rec["p99_ms"],
            "per_shard_published": rec["per_shard_published"],
            "conservation_ok": rec["conservation_ok"],
            "sharded_exact": rec["sharded_exact"],
        }
        _log(f"K={k}: {rec['ingest_edges_per_s_dedicated']:,.0f} ingest "
             f"edges/s (dedicated), {rec['achieved_qps']} qps, "
             f"p99 {rec['p99_ms']} ms")
        _emit(f"serve/sharded_k{k}",
              1e6 / max(rec["ingest_edges_per_s_dedicated"], 1e-9),
              f"ingest_eps={rec['ingest_edges_per_s_dedicated']};"
              f"qps={rec['achieved_qps']};p99_ms={rec['p99_ms']}")
    record = {
        "bench": "serve_sharded",
        "dataset": "cit-HepPh",
        "scale": scale,
        "budget_kb": 256,
        "depth": 5,
        # scaling is bounded by available cores: K > cpu_count adds thread
        # overhead without parallelism, so read the curve against this
        "cpu_count": os.cpu_count(),
        "shards": shards,
    }
    with open(out_path, "w") as f:
        _json.dump(record, f, indent=2)
    _log(f"wrote {out_path}")


# thread-backend sharded records from serve_sharded, keyed by (scale, K) —
# lets serve_process skip re-running benches an earlier target in the same
# `benchmarks.run` invocation already produced (CI runs the full sweep)
_SHARDED_THREAD_RECS: dict = {}


def serve_process(scale: float, quick: bool,
                  out_path: str = "BENCH_process.json") -> None:
    """Thread vs process runtime backends at K=1/2/4 -> BENCH_process.json.

    The GIL story in one artifact: the thread backend time-slices K shard
    workers inside one interpreter, the process backend gives each worker
    its own (ISSUE 5 tentpole).  Per (backend, K): dedicated backlog-drain
    ingest edges/s plus query p50/p99 under live ingest, with every sharded
    hard gate enforced (cross-shard conservation, merged-vs-replay
    bit-exactness, engine==direct).  Process K=4 vs K=1 scaling is recorded
    (cpu_count-contextualized) — no gate on absolute numbers: a 2-core CI
    box legitimately plateaus where a 16-core server keeps scaling.
    """
    import json as _json

    from benchmarks.serve_bench import run_serve_bench_sharded

    _log("\n== serve_process (thread vs process runtime backends) ==")
    backends: dict[str, dict] = {}
    for backend in ("thread", "process"):
        rows: dict[str, dict] = {}
        for k in (1, 2, 4):
            rec = (_SHARDED_THREAD_RECS.get((scale, k))
                   if backend == "thread" else None)
            if rec is None:
                # same load as serve_sharded, so reused thread rows and
                # fresh process rows stay apples-to-apples within the one
                # artifact (and standalone --only runs match the sweep)
                rec = run_serve_bench_sharded(
                    scale=scale, n_requests=600 if quick else 2000,
                    target_qps=1000.0 if quick else 2000.0, n_shards=k,
                    runtime_backend=backend)
            if not rec["conservation_ok"]:
                raise RuntimeError(
                    f"serve_process {backend} K={k}: cross-shard "
                    f"conservation failed (published "
                    f"{rec['published_edges']} + dropped "
                    f"{rec['dropped_edges']} != stream "
                    f"{rec['stream_total_edges']})")
            if rec["sharded_exact"] is False:
                raise RuntimeError(
                    f"serve_process {backend} K={k}: merged shard sketches "
                    "diverged from the single-sketch replay")
            if not rec["engine_matches_direct"]:
                raise RuntimeError(
                    f"serve_process {backend} K={k}: scatter/gather engine "
                    "diverged from the sharded direct oracle")
            if not rec["dedicated_ingest_conserved"]:
                raise RuntimeError(
                    f"serve_process {backend} K={k}: dedicated ingest "
                    "drain lost edges")
            rows[str(k)] = {
                "ingest_edges_per_s": rec["ingest_edges_per_s_dedicated"],
                "ingest_edges_per_s_during_serve":
                    rec["ingest_edges_per_s_during_serve"],
                "achieved_qps": rec["achieved_qps"],
                "p50_ms": rec["p50_ms"],
                "p99_ms": rec["p99_ms"],
                "conservation_ok": rec["conservation_ok"],
                "sharded_exact": rec["sharded_exact"],
            }
            _log(f"{backend:8s} K={k}: "
                 f"{rec['ingest_edges_per_s_dedicated']:,.0f} ingest "
                 f"edges/s (dedicated), p99 {rec['p99_ms']} ms")
            _emit(f"serve/{backend}_k{k}",
                  1e6 / max(rec["ingest_edges_per_s_dedicated"], 1e-9),
                  f"ingest_eps={rec['ingest_edges_per_s_dedicated']};"
                  f"qps={rec['achieved_qps']};p99_ms={rec['p99_ms']}")
        backends[backend] = rows
    p1 = backends["process"]["1"]["ingest_edges_per_s"]
    p4 = backends["process"]["4"]["ingest_edges_per_s"]
    record = {
        "bench": "serve_process",
        "dataset": "cit-HepPh",
        "scale": scale,
        "budget_kb": 256,
        "depth": 5,
        # scaling is bounded by available cores: K > cpu_count adds spawn +
        # scheduler overhead without parallelism, so read both curves (and
        # the thread-vs-process gap, which the GIL caps) against this
        "cpu_count": os.cpu_count(),
        "backends": backends,
        "process_k4_over_k1": round(p4 / max(p1, 1e-9), 3),
    }
    with open(out_path, "w") as f:
        _json.dump(record, f, indent=2)
    _log(f"wrote {out_path} (process K4/K1 = "
         f"{record['process_k4_over_k1']}x on {os.cpu_count()} cores)")


def serve_net(scale: float, quick: bool,
              out_path: str = "BENCH_net.json") -> None:
    """Network transport tier -> BENCH_net.json (DESIGN.md §Net).

    Three cells in one artifact:

      * ingest transport — the sharded serving bench on the ``socket``
        runtime backend (TCP self-host loopback workers) next to the
        ``process`` backend (mp pipes), with EVERY sharded hard gate
        enforced for both: cross-shard conservation, merged-vs-replay
        bit-exactness, engine==direct, dedicated-drain conservation.
        Same counters over a socket or a pipe, or the bench dies.
      * front-end — QPS/p50/p99 of the TCP query server at 1/2/4 client
        connections with the OFFERED load held constant (the loadgen's
        arrival clock is global), over one warmed live tenant.
      * overload — tiny ``max_inflight`` against a much higher offered
        rate: admission control must shed (nonzero, accounted — offered ==
        accepted + shed + errors on the client AND offered == admitted +
        shed on the server) while accepted-request p99 stays bounded
        instead of collapsing into queueing.
    """
    import json as _json

    from benchmarks.serve_bench import run_serve_bench_sharded
    from repro.net.query_server import QueryServer
    from repro.obs.hub import get_hub, reset_hub
    from repro.serving import (
        QueryEngine,
        SketchRegistry,
        mix_for_sketch,
        synth_requests,
        warm_bucket_ladder,
    )
    from repro.serving.loadgen import NetLoadGen

    _log("\n== serve_net (socket ingest transport + TCP query front-end) ==")

    def _wire_bytes() -> dict:
        """Parent-LOCAL wire byte counters (repro.net.wire instruments).

        Read ``state()``, never ``merged_state()``: the workers' shipped
        hub states are adopted alongside, and every frame is counted on
        both ends — merging would double the totals."""
        sent: dict[str, int] = {}
        recv: dict[str, int] = {}
        publish_bytes = 0
        for name, labels, value in get_hub().state()["counters"]:
            kind = labels.get("kind", "?")
            if name == "wire_bytes_sent":
                sent[kind] = sent.get(kind, 0) + int(value)
            elif name == "wire_bytes_recv":
                recv[kind] = recv.get(kind, 0) + int(value)
            elif name == "publish_bytes":
                publish_bytes += int(value)
        return {"sent": sent, "recv": recv, "publish_bytes": publish_bytes}

    # ---- cell 1: socket vs process ingest transport, gates on -------------
    transports: dict[str, dict] = {}
    for backend in ("process", "socket"):
        reset_hub()  # per-cell wire accounting (parent-local)
        rec = run_serve_bench_sharded(
            scale=scale, n_requests=400 if quick else 1500,
            target_qps=1000.0 if quick else 2000.0, n_shards=2,
            runtime_backend=backend, ingest_repeats=3)
        if not rec["conservation_ok"]:
            raise RuntimeError(
                f"serve_net {backend} transport: cross-shard conservation "
                f"failed (published {rec['published_edges']} + dropped "
                f"{rec['dropped_edges']} != stream "
                f"{rec['stream_total_edges']})")
        if rec["sharded_exact"] is False:
            raise RuntimeError(
                f"serve_net {backend} transport: merged shard sketches "
                "diverged from the single-sketch replay — the transport "
                "changed what was counted")
        if not rec["engine_matches_direct"]:
            raise RuntimeError(
                f"serve_net {backend} transport: scatter/gather engine "
                "diverged from the sharded direct oracle")
        if not rec["dedicated_ingest_conserved"]:
            raise RuntimeError(
                f"serve_net {backend} transport: dedicated ingest drain "
                "lost edges")
        wire_bytes = _wire_bytes()
        transports[backend] = {
            "ingest_edges_per_s": rec["ingest_edges_per_s_dedicated"],
            "ingest_edges_per_s_during_serve":
                rec["ingest_edges_per_s_during_serve"],
            "achieved_qps": rec["achieved_qps"],
            "p99_ms": rec["p99_ms"],
            "conservation_ok": rec["conservation_ok"],
            "sharded_exact": rec["sharded_exact"],
            "wire_bytes": wire_bytes,
        }
        _log(f"{backend:8s} transport: "
             f"{rec['ingest_edges_per_s_dedicated']:,.0f} ingest edges/s "
             f"(dedicated), p99 {rec['p99_ms']} ms, "
             f"publish_bytes {wire_bytes['publish_bytes']:,}")
        _emit(f"net/ingest_{backend}",
              1e6 / max(rec["ingest_edges_per_s_dedicated"], 1e-9),
              f"ingest_eps={rec['ingest_edges_per_s_dedicated']};"
              f"qps={rec['achieved_qps']};p99_ms={rec['p99_ms']}")

    # ---- cell 1b: delta vs full publish payloads (A/B, gate on) -----------
    # same stream, same every:1 policy, process backend; only the publish
    # encoding differs.  Gates: delta must ship measurably fewer bytes per
    # epoch AND the final adopted sketches must be bit-identical — the
    # sparse delta path is an optimisation, never an approximation.
    import jax as _jax

    from repro.runtime import Runtime
    from repro.runtime.backend import ProcessBackend

    publish_rows: dict[str, dict] = {}
    finals: dict[str, object] = {}
    for mode in ("delta", "full"):
        reset_hub()
        t = SketchRegistry(depth=5, scale=scale).open(
            "cit-HepPh", "kmatrix", 256, seed=0)
        rt = Runtime(publish_policy="every:1", poll_s=0.01,
                     backend=ProcessBackend(publish_mode=mode))
        rt.attach(t)
        rt.start(pumps=False)
        rt.wait_ready()
        rt.start_pumps()
        rt.join_pumps()
        rep = rt.stop(drain=True)[t.key.tenant_id]
        if rep["unaccounted_edges"]:
            raise RuntimeError(
                f"serve_net publish mode={mode}: conservation failed "
                f"({rep['unaccounted_edges']} unaccounted edges)")
        pub_bytes = _wire_bytes()["publish_bytes"]
        epochs = int(rep.get("publishes") or 1)
        publish_rows[mode] = {
            "publish_bytes": pub_bytes,
            "epochs": epochs,
            "publish_bytes_per_epoch": round(pub_bytes / max(epochs, 1)),
        }
        finals[mode] = t.snapshot
        _log(f"publish mode={mode}: {pub_bytes:,} publish bytes over "
             f"{epochs} epochs "
             f"({publish_rows[mode]['publish_bytes_per_epoch']:,}/epoch)")
        _emit(f"net/publish_{mode}",
              publish_rows[mode]["publish_bytes_per_epoch"],
              f"publish_bytes={pub_bytes};epochs={epochs}")
    if not (0 < publish_rows["delta"]["publish_bytes_per_epoch"]
            < publish_rows["full"]["publish_bytes_per_epoch"]):
        raise RuntimeError(
            f"serve_net publish A/B: delta publishes are not smaller than "
            f"full ({publish_rows})")
    d_leaves = _jax.tree_util.tree_leaves(finals["delta"].sketch)
    f_leaves = _jax.tree_util.tree_leaves(finals["full"].sketch)
    if finals["delta"].n_edges != finals["full"].n_edges or not all(
            np.array_equal(np.asarray(a), np.asarray(b))
            for a, b in zip(d_leaves, f_leaves)):
        raise RuntimeError(
            "serve_net publish A/B: delta-adopted sketch diverged from "
            "full-adopted sketch — delta publication must be bit-exact")
    _log(f"publish A/B: delta/full bytes-per-epoch = "
         f"{publish_rows['delta']['publish_bytes_per_epoch'] / max(publish_rows['full']['publish_bytes_per_epoch'], 1):.3f}, "
         "final sketches bit-identical")

    # ---- warmed live tenant + engine shared by cells 2 and 3 --------------
    registry = SketchRegistry(depth=5, scale=scale)
    tenant = registry.open("cit-HepPh", "kmatrix", 256, seed=0)
    tenant.step(min(8, max(1, tenant.stream.num_batches // 2)))
    tenant.publish()
    n_nodes = tenant.stream.spec.n_nodes
    engine = QueryEngine()
    mix = mix_for_sketch("kmatrix")
    kw = dict(n_nodes=n_nodes, heavy_universe=min(n_nodes, 1 << 14),
              heavy_threshold=100.0)
    warm_bucket_ladder(engine, tenant.snapshot,
                       synth_requests(256, mix, seed=99, **kw))

    # ---- cell 2: QPS/p50/p99 vs connection count --------------------------
    n_req = 600 if quick else 2400
    qps = 500.0 if quick else 1000.0
    requests = synth_requests(n_req, mix, seed=11, **kw)
    conn_rows: dict[str, dict] = {}
    server = QueryServer(engine, lambda: tenant.snapshot,
                         info={"n_nodes": n_nodes, "kind": "kmatrix",
                               "dataset": "cit-HepPh"}).start()
    try:
        for conns in (1, 2, 4):
            rep = NetLoadGen(target_qps=qps, connections=conns,
                             batch_max=64).run(server.address, requests)
            if rep.errors:
                raise RuntimeError(
                    f"serve_net conns={conns}: {rep.errors} server-side "
                    "errors — QPS for failed answers is meaningless")
            if rep.aborted:
                raise RuntimeError(
                    f"serve_net conns={conns}: {rep.aborted} requests "
                    f"aborted on a dead transport ({rep.transport_error})")
            if rep.accepted != rep.n_requests:
                raise RuntimeError(
                    f"serve_net conns={conns}: {rep.shed} requests shed "
                    "under nominal load (max_inflight=4096) — admission "
                    "control is rejecting work it has room for")
            if rep.last_epoch is None:
                raise RuntimeError(
                    f"serve_net conns={conns}: answers carried no epoch "
                    "stamp — staleness contract broken")
            conn_rows[str(conns)] = {
                "achieved_qps": round(rep.achieved_qps, 1),
                "p50_ms": round(rep.p50_ms, 3),
                "p99_ms": round(rep.p99_ms, 3),
                "n_batches": rep.n_batches,
                "last_epoch": rep.last_epoch,
            }
            _log(f"conns={conns}: {rep.achieved_qps:,.0f} qps, "
                 f"p50 {rep.p50_ms:.2f} ms, p99 {rep.p99_ms:.2f} ms "
                 f"({rep.n_batches} calls)")
            _emit(f"net/conns_{conns}", rep.p50_ms * 1e3,
                  f"qps={rep.achieved_qps:.0f};p50_ms={rep.p50_ms:.3f};"
                  f"p99_ms={rep.p99_ms:.3f}")
    finally:
        server.stop()

    # ---- cell 3: overload — admission control must shed, accounted --------
    over = QueryServer(engine, lambda: tenant.snapshot, max_inflight=64,
                       batch_max=32,
                       info={"n_nodes": n_nodes, "kind": "kmatrix"}).start()
    try:
        over_reqs = synth_requests(800 if quick else 2000, mix, seed=23, **kw)
        rep = NetLoadGen(target_qps=qps * 10, connections=4,
                         batch_max=64).run(over.address, over_reqs)
        stats = over.stats()
    finally:
        over.stop()
    if rep.errors:
        raise RuntimeError(
            f"serve_net overload: {rep.errors} server-side errors — "
            "overload must shed at admission, not fail mid-execution")
    if rep.shed <= 0:
        raise RuntimeError(
            "serve_net overload: offered 10x nominal against "
            "max_inflight=64 and nothing was shed — admission control "
            "is not engaging")
    if rep.aborted:
        raise RuntimeError(
            f"serve_net overload: {rep.aborted} requests aborted on a "
            f"dead transport ({rep.transport_error}) — overload must shed "
            "at admission, not kill connections")
    if rep.accepted + rep.shed != rep.n_requests:
        raise RuntimeError(
            f"serve_net overload: client accounting leak ({rep.accepted} "
            f"accepted + {rep.shed} shed != {rep.n_requests} offered)")
    if stats["offered_requests"] != (stats["admitted_requests"]
                                     + stats["shed_overload"]
                                     + stats["shed_rate_limited"]
                                     + stats["shed_too_large"]):
        raise RuntimeError(
            f"serve_net overload: server admission ledger does not "
            f"balance ({stats})")
    if not np.isfinite(rep.p99_ms) or rep.p99_ms > 30_000:
        raise RuntimeError(
            f"serve_net overload: accepted-request p99 {rep.p99_ms} ms — "
            "shedding exists precisely so accepted work stays bounded")
    if rep.mean_retry_after_ms <= 0:
        raise RuntimeError(
            "serve_net overload: rejections carried no Retry-After hint")
    _log(f"overload: shed {rep.shed}/{rep.n_requests} "
         f"({rep.shed_rate:.1%}), accepted p99 {rep.p99_ms:.2f} ms, "
         f"mean retry-after hint {rep.mean_retry_after_ms:.1f} ms")
    _emit("net/overload", rep.p99_ms * 1e3,
          f"shed_rate={rep.shed_rate:.4f};p99_ms={rep.p99_ms:.3f};"
          f"retry_after_ms={rep.mean_retry_after_ms:.1f}")

    record = {
        "bench": "serve_net",
        "dataset": "cit-HepPh",
        "scale": scale,
        "budget_kb": 256,
        "depth": 5,
        "cpu_count": os.cpu_count(),
        "ingest_transports": transports,
        "socket_over_process": round(
            transports["socket"]["ingest_edges_per_s"]
            / max(transports["process"]["ingest_edges_per_s"], 1e-9), 3),
        "publish_bytes_per_epoch": {
            mode: row["publish_bytes_per_epoch"]
            for mode, row in publish_rows.items()},
        "publish_payload": publish_rows,
        "frontend_offered_qps": qps,
        "frontend_connections": conn_rows,
        "overload": {
            "offered_qps": qps * 10,
            "max_inflight": 64,
            "n_requests": rep.n_requests,
            "accepted": rep.accepted,
            "shed": rep.shed,
            "shed_rate": round(rep.shed_rate, 4),
            "p99_ms": round(rep.p99_ms, 3),
            "mean_retry_after_ms": round(rep.mean_retry_after_ms, 1),
            "server_stats": stats,
        },
    }
    with open(out_path, "w") as f:
        _json.dump(record, f, indent=2)
    _log(f"wrote {out_path} (socket/process ingest = "
         f"{record['socket_over_process']}x)")


def obs_overhead(scale: float, quick: bool,
                 out_path: str = "BENCH_obs.json") -> None:
    """Telemetry overhead -> BENCH_obs.json (DESIGN.md §Observability).

    Two arms over identical work, toggled with ``repro.obs.set_disabled``
    (the global instrument kill-switch): a thread-backend runtime ingest
    drain (edges/s) and an in-process open-loop query run (p99 ms).  Each
    arm takes the best of ``reps`` walls, alternating on/off so drift
    hits both arms equally.  Hard gate: metrics-on ingest throughput must
    stay within 5% of metrics-off — typed instruments are per-batch work
    (two counter incs, two histogram buckets, one span emit against
    ~8k-edge batches), so a bigger gap means someone put telemetry on the
    per-edge path.
    """
    import json as _json

    from repro.obs import reset_hub, reset_trace_log, set_disabled
    from repro.runtime import Runtime
    from repro.serving import (
        QueryEngine,
        SketchRegistry,
        mix_for_sketch,
        synth_requests,
        warm_bucket_ladder,
    )
    from repro.serving.loadgen import OpenLoopLoadGen

    _log("\n== obs (telemetry overhead: metrics on vs off) ==")
    reps = 2 if quick else 3

    def ingest_eps() -> float:
        reset_hub()
        reset_trace_log()
        registry = SketchRegistry(depth=5, scale=scale)
        tenant = registry.open("cit-HepPh", "kmatrix", 256, seed=0)
        runtime = Runtime(publish_policy="drain:0", reservoir_k=0,
                          backend="thread")
        runtime.attach(tenant)
        runtime.start(pumps=False)
        runtime.wait_ready()
        t0 = time.time()
        runtime.start_pumps()
        runtime.join_pumps()
        rep = runtime.stop(drain=True)[tenant.key.tenant_id]
        dt = time.time() - t0
        if rep["unaccounted_edges"]:
            raise RuntimeError("obs bench: ingest drain lost edges")
        return rep["ingested_edges"] / max(dt, 1e-9)

    def query_p99() -> float:
        reset_hub()
        registry = SketchRegistry(depth=5, scale=scale)
        tenant = registry.open("cit-HepPh", "kmatrix", 256, seed=0)
        tenant.step(min(4, max(1, tenant.stream.num_batches // 2)))
        tenant.publish()
        n_nodes = tenant.stream.spec.n_nodes
        engine = QueryEngine()
        mix = mix_for_sketch("kmatrix")
        kw = dict(n_nodes=n_nodes, heavy_universe=min(n_nodes, 1 << 14),
                  heavy_threshold=100.0)
        warm_bucket_ladder(engine, tenant.snapshot,
                           synth_requests(128, mix, seed=99, **kw))
        requests = synth_requests(400 if quick else 1500, mix, seed=11, **kw)
        report = OpenLoopLoadGen(
            target_qps=1000.0 if quick else 2000.0,
            batch_max=256).run(engine, lambda: tenant.snapshot, requests)
        return report.p99_ms

    arms = {"on": {"eps": 0.0, "p99_ms": float("inf")},
            "off": {"eps": 0.0, "p99_ms": float("inf")}}
    try:
        for _ in range(reps):
            for arm in ("off", "on"):  # alternate so drift hits both
                set_disabled(arm == "off")
                arms[arm]["eps"] = max(arms[arm]["eps"], ingest_eps())
                arms[arm]["p99_ms"] = min(arms[arm]["p99_ms"], query_p99())
    finally:
        set_disabled(False)
        reset_hub()
        reset_trace_log()

    ratio = arms["on"]["eps"] / max(arms["off"]["eps"], 1e-9)
    for arm in ("off", "on"):
        _log(f"metrics {arm:3s}: {arms[arm]['eps']:,.0f} ingest edges/s, "
             f"query p99 {arms[arm]['p99_ms']:.2f} ms")
        _emit(f"obs/metrics_{arm}", 1e6 / max(arms[arm]["eps"], 1e-9),
              f"ingest_eps={arms[arm]['eps']:.0f};"
              f"p99_ms={arms[arm]['p99_ms']:.3f}")
    _log(f"metrics-on/off ingest ratio: {ratio:.3f}")
    if ratio < 0.95:
        raise RuntimeError(
            f"obs bench: metrics-on ingest throughput is {ratio:.1%} of "
            "metrics-off (gate: within 5%) — telemetry has leaked onto "
            "the per-edge hot path")

    record = {
        "bench": "obs",
        "dataset": "cit-HepPh",
        "scale": scale,
        "budget_kb": 256,
        "depth": 5,
        "reps": reps,
        "metrics_on": {k: round(v, 3) for k, v in arms["on"].items()},
        "metrics_off": {k: round(v, 3) for k, v in arms["off"].items()},
        "on_over_off_ingest": round(ratio, 4),
        "gate_within": 0.05,
    }
    with open(out_path, "w") as f:
        _json.dump(record, f, indent=2)
    _log(f"wrote {out_path} (on/off ingest = {record['on_over_off_ingest']})")


BENCHES = {
    "fig6_build_time": lambda a: fig6_build_time(a.scale),
    "fig7_are": lambda a: fig7_fig8_accuracy(a.scale, a.quick),
    "partitioner_ablation": lambda a: partitioner_ablation(a.scale),
    "kernel_micro": lambda a: kernel_micro(a.quick),
    "ingest": lambda a: ingest_backends(a.scale, a.quick),
    "serve_mixed": lambda a: serve_mixed(a.scale, a.quick),
    "serve_concurrent": lambda a: serve_concurrent(a.scale, a.quick),
    "serve_sharded": lambda a: serve_sharded(a.scale, a.quick),
    "serve_process": lambda a: serve_process(a.scale, a.quick),
    "serve_net": lambda a: serve_net(a.scale, a.quick),
    "obs": lambda a: obs_overhead(a.scale, a.quick),
}


def main() -> None:
    from repro.launch.host import init_compile_cache

    init_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--scale", type=float, default=None,
                    help="dataset scale (default: 1.0, 0.1 with --quick)")
    ap.add_argument("--only", choices=sorted(BENCHES))
    args = ap.parse_args()
    if args.scale is None:
        args.scale = 0.1 if args.quick else 1.0
    print("name,us_per_call,derived")
    for name, fn in BENCHES.items():
        if args.only and name != args.only:
            continue
        fn(args)


if __name__ == "__main__":
    main()
