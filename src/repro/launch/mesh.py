"""Production mesh construction.

A function, not a module-level constant, so importing this module never
touches jax device state.  Single pod: 16x16 = 256 chips (data, model);
multi-pod: 2x16x16 = 512 chips (pod, data, model).  The dry-run
(launch/dryrun.py) sets XLA_FLAGS for 512 host placeholder devices *before*
importing jax; everything else sees the real device count.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(model: int = 1):
    """Whatever-is-available mesh for local smoke runs."""
    n = len(jax.devices())
    model = min(model, n)
    return jax.make_mesh((n // model, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
