"""Find a cell's configuration, traffic mix, reference and per-layer metric
readers by name, from files alone.

``BENCHMARK.json`` names each piece; the files live under ``bench/``:

    bench/configs/<config>.json      configuration (``file`` in BENCHMARK.json)
    bench/references/<name>.py       plain reference a configuration names
    bench/traffic/<traffic>.json     traffic mix, read by ``kbench.drive``
    bench/metrics/<metric>.py        per-layer metric reader: ``read(ctx)``

Adding any of them is adding a file and an entry; no file here changes.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path


def load_benchmark(root: Path) -> dict:
    return json.loads((Path(root) / "BENCHMARK.json").read_text())


def workload(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                   f"{[w['name'] for w in bench['workloads']]}")


def config(root: Path, bench: dict, name: str) -> dict:
    for c in bench["configs"]:
        if c["name"] == name:
            return json.loads((Path(root) / c["file"]).read_text())
    raise KeyError(f"no configuration {name!r} in BENCHMARK.json")


def traffic(bench_dir: Path, name: str) -> dict:
    return json.loads((Path(bench_dir) / "traffic" / f"{name}.json")
                      .read_text())


def _module(path: Path, name: str):
    if not path.is_file():
        raise FileNotFoundError(f"no file {path} for {name!r}")
    spec = importlib.util.spec_from_file_location(
        f"kbench_{path.parent.name}_{name}".replace(".", "_")
        .replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reference(bench_dir: Path, name: str):
    return _module(Path(bench_dir) / "references" / f"{name}.py", name)


def metric_reader(bench_dir: Path, name: str):
    return _module(Path(bench_dir) / "metrics" / f"{name}.py", name)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def end_to_end_for(bench: dict, cell: str) -> list:
    return [m for m in bench["end_to_end"] if _applies(m, cell)]


def per_layer_for(bench: dict, cell: str) -> list:
    """Per-layer metrics of a cell: those that list it, and those with no
    list whose end-to-end metric the cell reports."""
    e2e = {m["name"] for m in end_to_end_for(bench, cell)}
    return [m for m in bench["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in e2e)]
