"""The paper's experiments on the CPU — one function per table or figure.

Prints ``name,us_per_call,derived`` CSV rows (plus figure tables to stderr).

  fig6_build_time      paper Fig. 6 — time to add each dataset, per sketch
  fig7_are             paper Fig. 7 (ARE) and Fig. 8 (effective queries)
                       vs memory budget, Type II sketches
  partitioner_ablation beyond the paper: greedy (Eq. 8) vs banded sqrt-G

Nothing here gates. The accuracy columns are the reproduction; the Fig. 6
edges/s are host timings (the width-class column runs its Pallas kernel in
interpret mode off a TPU) and say nothing about the chip. Speed is measured
by the chip benchmark (``bench/``, declared in ``BENCHMARK.json``).

Usage: PYTHONPATH=src python -m benchmarks.run [--quick] [--only fig7_are]
"""
from __future__ import annotations

import argparse
import sys
import time

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


BENCHES = {
    "fig6_build_time": lambda a: fig6_build_time(a.scale),
    "fig7_are": lambda a: fig7_fig8_accuracy(a.scale, a.quick),
    "partitioner_ablation": lambda a: partitioner_ablation(a.scale),
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
