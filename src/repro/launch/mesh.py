"""Production mesh construction.

A FUNCTION, not a module constant — importing this module never touches jax
device state (jax locks the device count on first backend init, and smoke
tests must see 1 device while the dry-run sees 512).
"""
from __future__ import annotations

import math
from typing import Optional, Tuple


def make_production_mesh(*, multi_pod: bool = False):
    """(16,16) data x model per pod; (2,16,16) pod x data x model multi-pod."""
    import jax
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_mesh_for(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    return _mesh(shape, axes)


def make_local_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many (possibly virtual) devices exist."""
    return _mesh((data, model), ("data", "model"))


def _mesh(shape, axes):
    import jax
    from jax.sharding import AxisType
    n = math.prod(shape)
    devs = jax.devices()
    if len(devs) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices, have {len(devs)} — the dry-run "
            "entrypoint must set XLA_FLAGS=--xla_force_host_platform_device_"
            "count before importing jax")
    return jax.make_mesh(shape, axes, devices=devs[:n],
                         axis_types=(AxisType.Auto,) * len(axes))
