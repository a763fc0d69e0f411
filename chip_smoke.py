"""Bring-up smoke: the kMatrix ingest and serving path on one TPU chip.

    python chip_smoke.py               # one chip: ingest + serving phases
    python chip_smoke.py --four-chips  # four chips: distributed ingest only

Everything runs in this one process (a chip serves one process at a time),
through the entry points a user calls:

  ingest    ``launch/stream_ingest.py``'s inline pipeline (sample ->
            partition -> jitted ingest -> ARE) on cit-HepPh and email-EuAll
            at scale 1.0, d=7, at 512 KB and 64 MiB.  Each run ingests the
            whole stream through the width-class ``pallas`` layout and,
            over the same cells, through the ``flat`` scatter layout; the
            counters must be bit-identical, every layer must hold the
            stream's whole edge mass, and the compiled ``pallas`` step must
            contain a ``tpu_custom_call`` (the Mosaic kernel).
  serving   one tenant through ``launch/query_serve.py`` with background
            ingest on the thread backend (cit-HepPh, scale 1.0, 64 MiB):
            a few hundred mixed queries at a modest offered rate, then the
            run must conserve every edge, engine answers must equal direct
            answers, and the final snapshot must equal a single-sketch
            replay of the stream (``serving/gates.py``).
  four      (``--four-chips`` only) a 2x2 (data, model) mesh: data-parallel
            and partition-parallel all-gather ingest of cit-HepPh at scale
            1.0 must be bit-identical to a single-device ingest; the a2a
            mode's drop count is printed.

Each phase prints one JSON line naming the device.  The last line of
standard output is ``{"ok": true, "device": {...}}`` and appears only when
JAX runs on a TPU and every phase passed; otherwise the script exits
non-zero.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import traceback
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

INGEST_RUNS = [(ds, kb) for ds in ("cit-HepPh", "email-EuAll")
               for kb in (512, 64 * 1024)]
SERVE_BUDGET_KB = 64 * 1024
SERVE_REQUESTS = 400
SERVE_QPS = 200.0
CHECK_REQUESTS = 64


def emit(record: dict) -> None:
    from repro.launch.host import device_info

    print(json.dumps({**record, "device": device_info()}), flush=True)


def ingest_phase(dataset: str, budget_kb: int, *, scale: float = 1.0) -> dict:
    """Both layouts over the same cells through the inline pipeline."""
    import numpy as np

    from repro.core import kmatrix
    from repro.core import kmatrix_accel as kma
    from repro.launch import stream_ingest
    from repro.serving.gates import layout_counters_equal

    args = stream_ingest.parse_args([
        "--dataset", dataset, "--budget-kb", str(budget_kb),
        "--scale", str(scale), "--depth", "7", "--sketch-backend", "pallas"])
    stream, empty, mod = stream_ingest.build_inline(args)
    assert mod is kma, mod
    t0 = time.perf_counter()
    accel, step = stream_ingest.run_inline_ingest(args, stream, empty, mod)
    flat, _ = stream_ingest.run_inline_ingest(
        args, stream, kma.to_flat_layout(empty), kmatrix)
    counters_equal = layout_counters_equal(kma.to_flat_layout(accel), flat)
    mass = [int(sum(np.asarray(p[r], np.int64).sum() for p in accel.pools))
            for r in range(accel.depth)]
    n_edges = int(stream.all_edges_numpy()[2].sum())
    hlo = step.lower(empty, stream.batch(0)).compile().as_text()
    are_pallas = stream_ingest.evaluate_are(args, stream, accel, mod)
    are_flat = stream_ingest.evaluate_are(args, stream, flat, kmatrix)
    record = {
        "phase": "ingest", "dataset": dataset, "budget_kb": budget_kb,
        "class_widths": list(accel.class_widths),
        "class_counts": list(accel.class_counts), "conn_w": accel.conn_w,
        "device_mib": accel.num_counters * 4 / 2**20,
        "stream_edges": n_edges,
        "overflow_edges": int(accel.overflow),
        "counters_equal": bool(counters_equal),
        "mass_ok": all(m == n_edges for m in mass),
        "tpu_custom_call": "tpu_custom_call" in hlo,
        "are_pallas": are_pallas, "are_flat": are_flat,
        "wall_s": time.perf_counter() - t0,
    }
    record["ok"] = (record["counters_equal"] and record["mass_ok"]
                    and record["tpu_custom_call"] and are_pallas == are_flat)
    return record


def serving_phase(*, scale: float = 1.0, budget_kb: int = SERVE_BUDGET_KB,
                  n_requests: int = SERVE_REQUESTS) -> dict:
    """One background-ingest tenant through query_serve, then the gates."""
    from repro.launch import query_serve
    from repro.serving import engine as eng
    from repro.serving.gates import (mismatched_indices, replay_exactness,
                                     replay_sketch)

    args = query_serve.parse_args([
        "--dataset", "cit-HepPh", "--budget-kb", str(budget_kb),
        "--scale", str(scale), "--depth", "7", "--n-requests",
        str(n_requests), "--qps", str(SERVE_QPS), "--background-ingest",
        "--runtime-backend", "thread"])
    t0 = time.perf_counter()
    summary, tenant, engine, requests = query_serve.serve_tenant(args)
    snap = tenant.snapshot
    check = requests[:CHECK_REQUESTS]
    got = [r.value for r in engine.execute(snap, check)]
    want = eng.direct_answers(snap, check)
    mismatched = mismatched_indices(got, want)
    replay = replay_sketch(tenant.mod, tenant.mod.empty_like(snap.sketch),
                           tenant.stream, tenant.stream.num_batches)
    verdict = replay_exactness(snap, replay, check, answers=want)
    families = sorted({r.family for r in requests})
    record = {
        "phase": "serving", "dataset": "cit-HepPh", "budget_kb": budget_kb,
        "sketch_backend": summary["sketch_backend"],
        "runtime_backend": summary["runtime_backend"],
        "worker_placement": summary["worker_placement"],
        "families": families, "n_requests": summary["n_requests"],
        "offered_qps": summary["offered_qps"],
        "achieved_qps": summary["achieved_qps"],
        "p50_ms": summary["p50_ms"], "p99_ms": summary["p99_ms"],
        "final_epoch": summary["final_epoch"],
        "published_edges": summary["published_edges"],
        "stream_total_edges": summary["stream_total_edges"],
        "conservation_ok": summary["conservation_ok"],
        "engine_equals_direct": not mismatched,
        "replay_counters_equal": verdict["counters_equal"],
        "replay_estimates_equal": verdict["estimates_equal"],
        "wall_s": time.perf_counter() - t0,
    }
    record["ok"] = bool(record["conservation_ok"]
                        and record["engine_equals_direct"] and verdict["ok"])
    return record


def four_chip_phase(*, scale: float = 1.0) -> dict:
    """DP and PP ingest on a 2x2 mesh against a single-device ingest."""
    import jax

    from repro.core import kmatrix
    from repro.distributed.exactness import check_modes
    from repro.launch import stream_ingest
    from repro.streams import sample_stream

    args = stream_ingest.parse_args([
        "--dataset", "cit-HepPh", "--scale", str(scale), "--depth", "7",
        "--sketch-backend", "flat"])
    stream, sk0, mod = stream_ingest.build_inline(args)
    assert mod is kmatrix, mod
    qs, qd, _ = sample_stream(stream, 4096, seed=9)
    mesh = jax.make_mesh((2, 2), ("data", "model"))
    t0 = time.perf_counter()
    verdict = check_modes(sk0, stream, mesh, qs, qd)
    record = {"phase": "four_chips", "dataset": "cit-HepPh",
              "budget_kb": args.budget_kb, "mesh": dict(mesh.shape),
              **verdict, "wall_s": time.perf_counter() - t0}
    record["ok"] = bool(record["dp_exact"] and record["pp_allgather_exact"]
                        and record["a2a_never_overcounts"])
    return record


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 2x2-mesh distributed ingest phase")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(SRC))
    from repro.launch.host import device_info, init_compile_cache

    cache_dir = init_compile_cache()
    import jax

    device = device_info()
    if device["platform"] != "tpu":
        print(f"chip_smoke: JAX runs on {device['platform']}, not a TPU; "
              "nothing was run", file=sys.stderr)
        return 2
    need = 4 if args.four_chips else 1
    if device["count"] < need:
        print(f"chip_smoke: {need} TPU devices needed, JAX sees "
              f"{device['count']}", file=sys.stderr)
        return 2

    cache = {"hits": 0, "misses": 0}

    def count(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            cache["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            cache["misses"] += 1

    jax.monitoring.register_event_listener(count)
    if args.four_chips:
        phases = [("four_chips", four_chip_phase)]
    else:
        phases = [(f"ingest {ds} {kb}KB",
                   lambda ds=ds, kb=kb: ingest_phase(ds, kb))
                  for ds, kb in INGEST_RUNS]
        phases.append(("serving", serving_phase))
    failed = []
    for name, phase in phases:
        try:
            record = phase()
        except Exception:  # noqa: BLE001 — report every phase, then fail
            traceback.print_exc()
            record = {"phase": name, "ok": False, "error": "exception"}
        emit(record)
        if not record["ok"]:
            failed.append(name)
    emit({"phase": "compile_cache", "dir": cache_dir, **cache})
    if failed:
        print(f"chip_smoke: failed phases: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
