"""Mesh construction.

FUNCTIONS (not module-level constants) so importing this module never
touches jax device state — required because the dry-run must set
XLA_FLAGS before any jax initialisation.

Every mesh has ``Auto`` axes: the model code places arrays with
``with_sharding_constraint`` (``runtime/sharding.py:constrain``), which
takes only ``Auto`` axes, while ``jax.make_mesh`` defaults to ``Explicit``.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...], devices=None):
    """``jax.make_mesh`` with ``Auto`` axes (over ``devices`` when given)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips with a leading 'pod'
    axis for cross-pod data parallelism."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)
