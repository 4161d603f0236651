"""Production mesh construction. A FUNCTION (not a module constant) so that
importing never touches jax device state — the dry-run overrides the device
count before first jax init, smoke tests see the single real device."""
from __future__ import annotations

import jax

__all__ = ["make_production_mesh", "make_test_mesh"]


def _make_mesh(shape, axes):
    auto = (jax.sharding.AxisType.Auto,) * len(axes)
    return jax.make_mesh(shape, axes, axis_types=auto)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips when multi_pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_test_mesh(data: int = 2, model: int = 2, pod: int = 0):
    """Small mesh for CI-sized sharding tests (host devices)."""
    if pod:
        return _make_mesh((pod, data, model), ("pod", "data", "model"))
    return _make_mesh((data, model), ("data", "model"))
