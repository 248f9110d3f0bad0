"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so
importing this module never touches jax device state; the dry-run driver
sets ``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any
jax import to obtain placeholder devices.
"""
from __future__ import annotations

from typing import Tuple

import jax

from repro.models.sharding import ShardCtx


def auto_mesh(shape, axes):
    """``jax.make_mesh`` with every axis Auto: shardings are propagated by
    the compiler from ``NamedSharding`` annotations, as this code expects
    (``jax.make_mesh`` defaults to Explicit axes)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_shard_ctx(mesh) -> ShardCtx:
    axes = mesh.axis_names
    dp_axes: Tuple[str, ...] = tuple(a for a in axes if a != "model")
    return ShardCtx(mesh=mesh, dp_axes=dp_axes, model_axis="model")


def make_host_mesh():
    """Single-process mesh over whatever devices exist (tests/examples)."""
    n = len(jax.devices())
    return auto_mesh((n, 1), ("data", "model"))
