"""Process set-up shared by every entry point: compile cache and device report.

Both launchers, the benchmarks and ``chip_smoke.py`` call
``init_compile_cache()`` first thing in ``main()``; nothing here runs at
import.  ``device_info()`` is the device stamp every summary line carries, so
a CPU run can never be mistaken for a chip run.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_ROOT = Path(__file__).resolve().parents[3]
CACHE_DIR = REPO_ROOT / ".jax_cache"  # listed in .gitignore


def init_compile_cache() -> str:
    """Turn on JAX's persistent compile cache; returns its directory.

    ``$JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX itself and wins:
    nothing else is set.  Otherwise the cache lives at the fixed in-checkout
    path ``CACHE_DIR`` — never a temp name, pid or time, since a directory
    that moves between runs never hits.
    """
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)


def device_info() -> dict:
    """``{"platform", "kind", "count"}`` of the devices JAX runs on."""
    devices = jax.devices()
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices)}
