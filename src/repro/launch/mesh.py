"""Production mesh factories.

Defined as functions (never module-level constants) so importing this module
never touches jax device state — required because the dry-run must set
XLA_FLAGS before any jax initialization.
"""
from __future__ import annotations

import jax  # repro: noqa RPR001 -- launch-time mesh module; only reached from train-arch entry points

from jax.sharding import AxisType  # repro: noqa RPR001 -- launch-time mesh module


def _axis_kwargs(n_axes: int) -> dict:
    return {"axis_types": (AxisType.Auto,) * n_axes}


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; multi_pod adds the 2-pod axis (512 chips).

    Axes: 'pod' (DCI data parallel), 'data' (ICI data/FSDP), 'model' (ICI
    tensor/expert parallel).
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, **_axis_kwargs(len(axes)))


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over whatever devices exist (tests / CPU smoke)."""
    n = len(jax.devices())
    data = min(data, n)
    model = min(model, max(n // data, 1))
    return jax.make_mesh((data, model), ("data", "model"), **_axis_kwargs(2))


def dp_size(mesh) -> int:
    s = 1
    for name in ("pod", "data"):
        if name in mesh.axis_names:
            s *= mesh.shape[name]
    return s
