#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print one result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json`` ``workloads``) names a configuration and a
traffic mix; both, the configuration's plain reference and the per-layer
metric readers are found by name under ``bench/`` (``kbench.discover``).
The run loads the tenant and warms every shape (``setup_s``), measures for
``--seconds`` through the program's served path (``kbench.drive``), drains,
then checks the final counters, the edge count and, where the mix has
queries, a seeded sample of the engine's answers against the reference.

With ``--trace 0`` the result carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics read from a profiler trace of the
window.  The last line of standard output is the JSON result; the numbers
compared with their limits are the last lines of standard error.  Off a TPU,
or with fewer chips than the cell asks for, it exits 3 and prints no result.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path[:0] = [str(BENCH), str(ROOT / "src")]

import numpy as np  # noqa: E402

from kbench import discover, drive, stats  # noqa: E402
from kbench import trace as tracing  # noqa: E402
from kbench.drive import log  # noqa: E402


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def device_report() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> int | None:
    import jax

    peaks = []
    for d in jax.local_devices():
        ms = d.memory_stats() or {}
        if "peak_bytes_in_use" in ms:
            peaks.append(int(ms["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def end_to_end(cell, w) -> dict:
    """Every end-to-end number the window supports, by metric name."""
    out = {}
    if cell.traffic["ingest"]["mode"] == "saturate":
        out["ingest_edges_per_s"] = stats.rate_over(w.publishes, w.t_open,
                                                    w.t_close)
    due = [b for b in w.batches if w.t_open <= b[0] < w.t_close]
    if cell.traffic["ingest"]["mode"] == "rate" and due:
        seen = stats.visibility_times(w.publishes, [b[4] for b in due])
        out["freshness_p90_ms"] = stats.percentile(
            [(v - b[0]) * 1e3 for v, b in zip(seen, due) if v is not None],
            90)
    lat = query_latencies(cell, w)
    if lat is not None:
        out["query_p95_ms"] = stats.percentile(lat * 1e3, 95)
    return out


def query_latencies(cell, w):
    """Seconds from due to answer for every query due in the window."""
    if not cell.traffic.get("queries"):
        return None
    interval = 1.0 / float(cell.traffic["queries"]["qps"])
    lat = []
    for i, j, _, t_b, *_ in w.query_batches:
        due = cell._t_traffic + np.arange(i, j) * interval
        keep = (due >= w.t_open) & (due < w.t_close)
        lat.extend((t_b - due[keep]).tolist())
    return np.asarray(lat)


def diagnostics(cell, w) -> dict:
    """How the window went: producer and query-generator lateness, queue
    starvation, compiles, epochs, the layout that ran."""
    from repro.core.queries import closure_backend

    due = [b for b in w.batches if w.t_open <= b[0] < w.t_close]
    late = [b[1] - b[0] for b in due]
    pubs = [p for p in w.publishes if w.t_open <= p[0] < w.t_close]
    wake = [x for t, x in w.query_wake_late if w.t_open <= t < w.t_close]
    raw = w.dedup_end[0] - w.dedup_start[0]
    uniq = w.dedup_end[1] - w.dedup_start[1]
    return {
        "sketch_layout": cell.layout,
        "closure_backend": closure_backend(),
        "client_batches_in_window": len(due),
        "submitted_edges_total": cell.submitted_edges,
        "epochs_in_window": len(pubs),
        "producer_late_p99_ms": stats.percentile(late, 99) * 1e3
        if cell.traffic["ingest"]["mode"] == "rate" else None,
        "producer_late_max_ms": max(late) * 1e3
        if late and cell.traffic["ingest"]["mode"] == "rate" else None,
        "queue_ran_empty_batches": sum(1 for b in due if b[3] == 0),
        "query_batches_in_window": sum(
            1 for b in w.query_batches if w.t_open <= b[2] < w.t_close),
        "query_generator_late_max_ms": max(wake) * 1e3 if wake else None,
        "query_errors": w.query_errors,
        "compiles_in_window": sum(1 for t, _ in w.compiles
                                  if w.t_open <= t < w.t_close),
        "dedup_rows_in_window": [raw, uniq],
        "setup_phases_s": cell.setup_phases,
    }


def check(cell, ref, w, n_published: int, program: dict,
          seed: int) -> tuple[dict, int, int]:
    """Compare what the timed path produced with the plain reference.

    Returns ({name: (value, limit)}, attempted, failed)."""
    layout = ref.Layout(cell.cfg, cell.layout)
    want = ref.counters_after(layout, cell.lap, cell.submitted_edges)
    mismatch = ref.count_mismatches(layout, program, want)
    del want
    unpublished = abs(cell.submitted_edges - n_published)
    checks = {"counter_mismatch_cells": (mismatch, 0),
              "unpublished_edges": (unpublished, 0)}
    due = [b for b in w.batches if w.t_open <= b[0] < w.t_close]
    attempted = len(due)
    failed = -(-unpublished // cell.traffic["client_batch"])
    q = cell.traffic.get("queries")
    if q:
        answers = ref.Answers(layout, cell.lap)
        interval = 1.0 / float(q["qps"])
        pool = []
        for b_idx, (i, j, _, _, n_edges, _, values) in \
                enumerate(w.query_batches):
            for x in range(i, j):
                if w.t_open <= cell._t_traffic + x * interval < w.t_close:
                    pool.append((b_idx, x))
        attempted += len(pool)
        rng = np.random.default_rng(seed)
        take = min(len(pool), int(q["check_answers"]))
        picks = set(rng.choice(len(pool), take, replace=False).tolist())
        # every family the window served is in the sample, heavy sweeps too
        first: dict = {}
        for k in rng.permutation(len(pool)).tolist():
            fam = cell.requests[pool[k][1] % len(cell.requests)].family
            first.setdefault(fam, k)
        chosen = [pool[k] for k in sorted(picks | set(first.values()))]
        wrong = 0
        for b_idx, x in chosen:
            i, j, _, _, n_edges, _, values = w.query_batches[b_idx]
            if values is None:
                wrong += 1
                continue
            req = cell.requests[x % len(cell.requests)]
            if not ref.same_answer(values[x - i],
                                   answers.answer(req, n_edges)):
                wrong += 1
        unanswered = sum(j - i for i, j, *_, values in w.query_batches
                         if values is None)
        failed += unanswered
        checks["wrong_answers"] = (wrong, 0)
    return checks, attempted, failed


def read_per_layer(bench_dir: Path, metrics: list, ctx) -> dict:
    out = {}
    for m in metrics:
        value = discover.metric_reader(bench_dir, m["name"]).read(ctx)
        if value is None:
            log(f"per-layer metric {m['name']}: nothing to read")
            continue
        out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def main(argv=None, *, root: Path = ROOT, require_tpu: bool = True) -> int:
    args = parse_args(argv)
    root = Path(root)
    bench_dir = root / "bench"
    bench = discover.load_benchmark(root)
    spec = discover.workload(bench, args.workload)
    cfg = discover.config(root, bench, spec["config"])
    traffic = discover.traffic(bench_dir, spec["traffic"])
    ref = discover.reference(bench_dir, cfg["reference"])

    import jax

    if require_tpu:
        from repro.launch.host import init_compile_cache

        init_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    device = device_report()
    if require_tpu and (device["platform"] != "tpu"
                        or device["count"] < spec["chips"]):
        log(f"{args.workload} needs {spec['chips']} TPU chip(s); JAX sees "
            f"{device['count']} {device['platform']} device(s). No result.")
        return 3
    peaks = stats.load_peaks(device["kind"]) if require_tpu else None

    cell = drive.Cell(cfg, traffic, args.seed)
    cell.setup()
    trace_dir = tempfile.mkdtemp(prefix="kbench-trace-") if args.trace \
        else None
    try:
        w = cell.run(args.seconds, trace_dir)
        setup_s = w.t_open - T_PROCESS
        device["memory_peak_bytes"] = memory_peak_bytes()
        n_published, program = cell.final_counters()
        e2e = end_to_end(cell, w)
        diag = diagnostics(cell, w)
        if args.trace:
            tr = tracing.load(trace_dir)
            ctx = SimpleNamespace(trace=tr, window=w, cell=cell, peaks=peaks,
                                  cfg=cfg, traffic=traffic)
            per_layer = read_per_layer(
                bench_dir, discover.per_layer_for(bench, args.workload), ctx)
            device["busy_s"] = tr.busy_s()
            device["window_s"] = tr.window_s
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    cell.release()
    gc.collect()
    t_ref = time.perf_counter()
    checks, attempted, failed = check(cell, ref, w, n_published, program,
                                      args.seed)
    diag["reference_s"] = time.perf_counter() - t_ref
    correct = all(value <= limit for value, limit in checks.values())

    if args.trace:
        metrics = per_layer
    else:
        values = dict(e2e, setup_s=setup_s)
        metrics = {}
        for m in discover.end_to_end_for(bench, args.workload):
            v = values.get(m["name"])
            if v is None or not np.isfinite(v):
                log(f"end-to-end metric {m['name']}: not measured")
                continue
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    print(json.dumps({"diagnostics": diag}), flush=True)
    log(json.dumps({"diagnostics": diag}))
    result = {"correct": bool(correct), "attempted": int(attempted),
              "failed": int(failed), "metrics": metrics, "device": device}
    if args.trace:
        result["breakdown"] = tracing.breakdown(tr)
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    for k, (v, lim) in checks.items():
        log(f"check {k}: {v} (limit {lim})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
