"""Production mesh builders.

Functions (never module-level constants) so importing this module never
touches jax device state — device count is locked at first jax init, and the
dry-run needs to set XLA_FLAGS before that happens.

Every mesh has ``AxisType.Auto`` axes: the model code places arrays with
``with_sharding_constraint`` and lets GSPMD propagate, which explicit axes
(``jax.make_mesh``'s default since jax 0.7) refuse.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import AxisType


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    """Arbitrary mesh (tests use small host-device meshes like (2, 4))."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips.
    Multi-pod:  (pod=2, data=16, model=16) = 512 chips."""
    if multi_pod:
        return make_mesh((2, 16, 16), ("pod", "data", "model"))
    return make_mesh((16, 16), ("data", "model"))


def make_host_mesh(data: int = 1, model: int = 1, pods: Optional[int] = None):
    """Mesh over however many host devices exist (CPU test path)."""
    if pods:
        return make_mesh((pods, data, model), ("pod", "data", "model"))
    return make_mesh((data, model), ("data", "model"))
