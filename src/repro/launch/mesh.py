"""Production mesh construction.

A function (not a module-level constant) so importing never touches jax
device state.  Single pod: 16×16 = 256 chips (data × model).  Multi-pod:
2×16×16 = 512 chips (pod × data × model).
"""

from __future__ import annotations

import jax


def make_mesh_auto(shape, axes):
    """``jax.make_mesh`` with every axis of type Auto (GSPMD chooses the
    collectives)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh_auto(shape, axes)


def make_smoke_mesh(n_devices: int | None = None):
    """Tiny mesh over whatever devices exist (tests / examples)."""
    n = n_devices or len(jax.devices())
    return make_mesh_auto((1, n), ("data", "model"))
