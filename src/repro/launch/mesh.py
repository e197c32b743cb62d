"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state.  Single pod: 16x16 = 256 chips (data, model).
Multi-pod: 2 pods x 256 = 512 chips (pod, data, model) — "pod" is the
slowest-varying axis (DCN-friendly outer data axis).  Every axis is
``AxisType.Auto``: XLA's sharding propagation places the collectives.
"""

from __future__ import annotations

import numpy as np

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devs = jax.devices()
    if len(devs) < n:
        raise RuntimeError(
            f"need {n} devices for the production mesh, found {len(devs)} "
            "(dryrun.py must set XLA_FLAGS before any jax import)"
        )
    return make_mesh(shape, axes, devices=devs[:n])


def make_mesh(shape, axes, devices=None):
    """Generic helper for tests/examples (Auto axis types)."""
    devs = devices if devices is not None else jax.devices()[: int(np.prod(shape))]
    return jax.make_mesh(
        tuple(shape), tuple(axes), devices=devs,
        axis_types=(AxisType.Auto,) * len(axes),
    )
