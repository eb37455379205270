"""Production meshes.

Single pod:  (16, 16)      axes ("data", "model")      — 256 chips (v5e pod)
Multi-pod:   (2, 16, 16)   axes ("pod", "data", "model") — 512 chips

``make_production_mesh`` is a function (never a module constant) so importing
this module touches no jax device state; the dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before first jax init.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape: tuple[int, ...], axes: tuple[str, ...]) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with Auto axes: the sharding code here places
    arrays with ``with_sharding_constraint``, which Explicit axes (the
    installed JAX's default) reject."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(model: int = 1) -> jax.sharding.Mesh:
    """Tiny mesh over whatever devices exist (tests / examples on CPU)."""
    n = len(jax.devices())
    model = min(model, n)
    return _auto_mesh((n // model, model), ("data", "model"))
