"""Mesh construction.

Functions (not module-level constants) so importing never touches jax
device state.
"""

from __future__ import annotations

import jax


def make_mesh_auto(shape, axes):
    """``jax.make_mesh`` with every axis of type Auto (GSPMD chooses the
    collectives)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_smoke_mesh(n_devices: int | None = None):
    """Tiny mesh over whatever devices exist (tests / examples)."""
    n = n_devices or len(jax.devices())
    return make_mesh_auto((1, n), ("data", "model"))
