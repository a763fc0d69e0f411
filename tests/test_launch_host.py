"""Entry-point set-up: the persistent compile cache location, and
``chip_smoke.py`` refusing to run anywhere but on a TPU."""
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.launch import host

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture
def cache_dir_config():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_honours_env_var(monkeypatch, tmp_path,
                                       cache_dir_config):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert host.init_compile_cache() == str(tmp_path)
    # JAX reads the variable itself; the helper sets no other directory
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_fixed_ignored_repo_path(monkeypatch,
                                                            cache_dir_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert host.CACHE_DIR == REPO / ".jax_cache"
    assert host.init_compile_cache() == str(REPO / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == str(REPO / ".jax_cache")
    ignored = (REPO / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored


@pytest.mark.parametrize("alone", [False, True])
def test_chip_smoke_fails_without_a_tpu(tmp_path, alone):
    """In the repo it refuses the CPU platform before running any phase;
    copied into a directory holding nothing else of the repo it cannot
    import the program at all.  Neither prints a result line."""
    script = REPO / "chip_smoke.py"
    if alone:
        script = Path(shutil.copy(script, tmp_path / "chip_smoke.py"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    proc = subprocess.run([sys.executable, str(script)], cwd=script.parent,
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert '"ok"' not in proc.stdout
    if alone:
        assert proc.returncode == 1, proc.stderr[-2000:]
        assert "No module named 'repro'" in proc.stderr, proc.stderr[-2000:]
    else:
        assert proc.returncode == 2, proc.stderr[-2000:]
        assert "JAX runs on cpu, not a TPU" in proc.stderr, proc.stderr[-2000:]
