"""Production meshes (functions only — importing never touches devices)."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single pod (256 chips) or 2x16x16 two-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, (AxisType.Auto,) * len(axes))


def make_host_mesh(data: int = 1, model: int = 1):
    """Tiny mesh over whatever devices exist (tests / examples)."""
    n = len(jax.devices())
    data = min(data, n)
    model = max(1, min(model, n // data))
    return jax.make_mesh((data, model), ("data", "model"),
                         (AxisType.Auto,) * 2)
