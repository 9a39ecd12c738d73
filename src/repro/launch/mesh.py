"""Production mesh construction.

A FUNCTION, not a module-level constant — importing this module never touches
jax device state (required by the dry-run contract).  Every axis is
``AxisType.Auto``: call sites pass explicit ``NamedSharding``s."""
from __future__ import annotations

import jax


def _make_mesh(shape, axes):
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_mesh(dp: int, tp: int, pods: int = 1):
    """Arbitrary mesh for examples / tests (1-device smoke: dp=tp=1)."""
    if pods > 1:
        return _make_mesh((pods, dp, tp), ("pod", "data", "model"))
    return _make_mesh((dp, tp), ("data", "model"))
