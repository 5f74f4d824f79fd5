"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — the dry-run sets XLA_FLAGS *before* first init.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def auto_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axes: the sharding rules here use
    ``with_sharding_constraint`` and partial specs, which the Explicit
    axes ``jax.make_mesh`` now defaults to reject."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many (CPU) devices exist — used by
    distributed tests running with XLA_FLAGS device oversubscription."""
    return auto_mesh((data, model), ("data", "model"))
