"""Mesh builders: the one place the repository makes a device mesh.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so that
importing this module never touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before first jax
init; tests and benches see the plain 1-device CPU.

Every mesh is built with ``AxisType.Auto`` axes: the model and train-step
code is written for GSPMD propagation (sharding constraints, not explicit
``out_sharding`` annotations), and ``jax.make_mesh`` would otherwise default
to ``Explicit`` axes.

Mesh shapes (assignment spec):
- single-pod:  (data=16, model=16)            = 256 chips (one v5e pod)
- multi-pod:   (pod=2, data=16, model=16)     = 512 chips (2 pods over DCN)

The ``pod`` axis is the slow (DCN) axis — collectives on it are what the
SCISPACE-style hierarchical schedules minimize.  Axis order is
pod → data → model so the fastest-varying mesh dim (model/TP) maps to
ICI-adjacent devices.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_mesh", "DEFAULT_SINGLE_POD", "DEFAULT_MULTI_POD"]

DEFAULT_SINGLE_POD: Tuple[int, ...] = (16, 16)
DEFAULT_MULTI_POD: Tuple[int, ...] = (2, 16, 16)


def make_production_mesh(*, multi_pod: bool = False):
    if multi_pod:
        return make_mesh(DEFAULT_MULTI_POD, ("pod", "data", "model"))
    return make_mesh(DEFAULT_SINGLE_POD, ("data", "model"))


def make_mesh(shape: Tuple[int, ...], axes: Optional[Tuple[str, ...]] = None):
    """Auto-axis mesh over the first ``prod(shape)`` devices (tests use tiny
    shapes like (2, 2)); ``axes`` defaults to the trailing names of
    ``("pod", "data", "model")``."""
    if axes is None:
        axes = ("pod", "data", "model")[-len(shape):]
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(shape))
