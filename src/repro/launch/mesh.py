"""Production mesh construction.

Kept as functions (never module-level constants) so importing this module
never touches jax device state — required because the dry-run must set
XLA_FLAGS before any jax initialization.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with every axis Auto: the sharding rules place
    arrays with ``NamedSharding`` and ``with_sharding_constraint`` and
    leave propagation to the compiler, which Explicit axes (the
    ``jax.make_mesh`` default) refuse."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(shape),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; multi_pod adds a leading 2-pod axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(data: int | None = None, model: int = 1):
    """Small mesh over whatever devices exist (tests / examples)."""
    n = len(jax.devices())
    if data is None:
        data = n // model
    return make_mesh((data, model), ("data", "model"))


def batch_axes(mesh) -> tuple[str, ...]:
    names = mesh.axis_names
    return tuple(a for a in ("pod", "data") if a in names)
