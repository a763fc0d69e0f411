"""A tiny copy of the benchmark for the CPU tests: the repo's ``bench/``
files plus a configuration and mixes small enough to run in seconds."""
from __future__ import annotations

import copy
import json
import shutil
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]

SCALE = 0.05  # of cit-HepPh: 1,727 nodes, 21,078 edges

CONFIG = {
    "name": "tiny", "source": "cit-HepPh at 5% scale", "reference": "kmatrix",
    "graph": {"dataset": "cit-HepPh", "n_nodes": int(34546 * SCALE),
              "n_edges": int(421578 * SCALE), "alpha_src": 1.05,
              "alpha_dst": 1.3, "graph_seed": 0, "scale": SCALE},
    "sketch": {"kind": "kmatrix", "depth": 3, "budget_kb": 64,
               "conn_frac": 0.1, "sample_size": 30000,
               "registry_batch_size": 8192, "partitioner": "banded",
               "n_bands": 16, "min_width": 8},
    "runtime": {"queue_capacity": 8, "backpressure": "block", "dedup": True},
}

INGEST = {"client_batch": 2048, "ingest": {"mode": "saturate"},
          "publish_policy": "every:4", "warm_epochs": 2}

SERVE = {"client_batch": 2048, "ingest": {"mode": "rate",
                                          "edges_per_s": 40000},
         "publish_policy": "drain", "warm_s": 0.5,
         "queries": {"qps": 40, "mix": {"edge_freq": 0.55, "reach": 0.25,
                                        "node_out": 0.10,
                                        "path_weight": 0.05,
                                        "subgraph_weight": 0.03,
                                        "heavy_nodes": 0.02},
                     "zipf_a": 1.2, "batch_max": 16,
                     "heavy_universe_max": 512, "heavy_threshold": 20.0,
                     "path_len": 4, "subgraph_edges": 3,
                     "check_answers": 60}}


def load_run():
    """``bench/run.py`` as a module (it is a script, not a package)."""
    import importlib.util

    spec = importlib.util.spec_from_file_location("kbench_run",
                                                  BENCH / "run.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def run_cell(root: Path, workload: str, seed: int, seconds: float = 1.5,
             trace: int = 0) -> dict:
    """Run a cell of a tiny root on the CPU; the parsed result line."""
    import contextlib
    import io

    run = load_run()
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = run.main(["--workload", workload, "--seed", str(seed),
                       "--seconds", str(seconds), "--trace", str(trace)],
                      root=root, require_tpu=False)
    assert rc == 0, rc
    return json.loads(out.getvalue().strip().splitlines()[-1])


def make_root(tmp: Path, config: dict | None = None,
              ingest: dict | None = None) -> Path:
    """A checkout-like directory: ``bench/`` copied, the tiny config and
    mixes added, and a BENCHMARK.json naming ``tiny.ingest`` and
    ``tiny.serve``."""
    tmp = Path(tmp)
    shutil.copytree(BENCH, tmp / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "test_*"))
    cfg = copy.deepcopy(config or CONFIG)
    (tmp / "bench" / "configs" / "tiny.json").write_text(json.dumps(cfg))
    (tmp / "bench" / "traffic" / "tiny-ingest.json").write_text(
        json.dumps(ingest or INGEST))
    (tmp / "bench" / "traffic" / "tiny-serve.json").write_text(
        json.dumps(SERVE))
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    bench["configs"] = [{"name": "tiny", "source": "test",
                         "file": "bench/configs/tiny.json", "reduced": [],
                         "why": "test"}]
    bench["workloads"] = [
        {"name": "tiny.ingest", "config": "tiny", "traffic": "tiny-ingest",
         "chips": 1, "why": "test"},
        {"name": "tiny.serve", "config": "tiny", "traffic": "tiny-serve",
         "chips": 1, "why": "test"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            kind = ".serve" if any(w.endswith(".serve")
                                   for w in m["workloads"]) else ".ingest"
            m["workloads"] = ["tiny" + kind]
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp
