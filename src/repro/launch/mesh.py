"""Production mesh construction.

A FUNCTION, not a module constant — importing this module never touches jax
device state (the dry-run sets ``xla_force_host_platform_device_count``
before first jax init; tests and benches must keep seeing 1 device).
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import AxisType, Mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """Single pod: (16, 16) = 256 chips, axes ("data", "model").
    Multi-pod: (2, 16, 16) = 512 chips, axes ("pod", "data", "model")."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...], *,
              devices: Optional[Sequence] = None) -> Mesh:
    """Arbitrary mesh with Auto axes (elastic runs / tests with few fake
    devices); ``devices`` defaults to all of ``jax.devices()``."""
    kw = {} if devices is None else {"devices": devices}
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes), **kw)


def mesh_from_config(cfg) -> Mesh:
    """RunConfig.mesh -> Mesh (production default, overridable for tests)."""
    if cfg.shape is not None:
        return make_mesh(cfg.shape, cfg.axes)
    return make_production_mesh(multi_pod=cfg.multi_pod)
