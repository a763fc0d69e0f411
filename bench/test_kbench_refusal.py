"""The harness refuses to measure off a TPU, and cannot run from a directory
that holds only the benchmark's own files."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "cit-hepph-1m.ingest", "--seed", "12345678901", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def _has_result(stdout):
    for line in stdout.splitlines():
        try:
            if "metrics" in json.loads(line):
                return True
        except ValueError:
            continue
    return False


def test_refuses_off_a_tpu():
    proc = _run(ROOT)
    assert proc.returncode == 3, proc.stderr[-2000:]
    assert "TPU" in proc.stderr
    assert not _has_result(proc.stdout)


def test_fails_with_only_the_benchmark_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(HERE, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {"PYTHONPATH": ""}
    proc = _run(tmp_path, env)
    assert proc.returncode != 0
    assert not _has_result(proc.stdout)
