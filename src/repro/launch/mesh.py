"""Production mesh construction.

Defined as functions (never module-level constants) so importing this
module never touches JAX device state — the dry-run driver must set
``XLA_FLAGS`` before the first device query.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


__all__ = ["make_production_mesh", "make_local_mesh", "DATA_AXES", "ALL_AXES"]

DATA_AXES = ("pod", "data")   # gradient / batch parallelism axes
ALL_AXES = ("pod", "data", "model")


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 single-pod (256 chips) or 2×16×16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_local_mesh(data: int | None = None, model: int = 1):
    """Mesh over whatever devices exist (tests, smoke runs, elastic restart)."""
    n = len(jax.devices())
    if data is None:
        data = n // model
    if data * model > n:
        raise ValueError(f"requested {data}×{model} mesh on {n} devices")
    return _mesh((data, model), ("data", "model"))
