"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — launch scripts set XLA_FLAGS before first init.

Production target: TPU v5e pods, 16x16 = 256 chips per pod.
  single-pod mesh: (data=16, model=16)
  multi-pod mesh:  (pod=2, data=16, model=16) = 512 chips
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_debug_mesh(n_devices: int | None = None):
    """Tiny mesh over whatever devices exist (tests/CI)."""
    n = n_devices or len(jax.devices())
    model = 1
    for cand in (4, 2, 1):
        if n % cand == 0:
            model = cand
            break
    return jax.make_mesh((n // model, model), ("data", "model"))


def dp_axes(mesh) -> tuple[str, ...]:
    """The data-parallel axis bundle: ('pod','data') on multi-pod meshes."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def all_axes(mesh) -> tuple[str, ...]:
    return tuple(mesh.axis_names)
