"""Seeded random weights, made on the device in one jitted call.

The layout is the architecture module's ``layout(dims)``: a nested dict
of ``(shape, init)`` leaves, checked against the system's parameter tree
in ``run.py``.  The values are the benchmark's own, by the leaf's init:

* ``ones``: every entry 1 (norm scales);
* ``zeros``: every entry 0;
* ``embed``: N(0, 0.02^2);
* ``fan_in``: N(0, 1/fan_in), the fan-in being the input width ``shape[-2]``;
* ``normal:<std>``: N(0, std^2), for a leaf whose fan-in is not ``shape[-2]``.

The plain reference reads the same arrays, so neither side takes anything
the program made.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

Layout = Dict[str, Any]  # nested dict of (shape, init) leaves


def _is_leaf(x) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[1], str)


def _init(key: jax.Array, shape: Tuple[int, ...], init: str, dtype) -> jax.Array:
    if init == "ones":
        return jnp.ones(shape, dtype)
    if init == "zeros":
        return jnp.zeros(shape, dtype)
    if init == "embed":
        std = 0.02
    elif init == "fan_in":
        std = shape[-2] ** -0.5
    elif init.startswith("normal:"):
        std = float(init.split(":", 1)[1])
    else:
        raise ValueError(f"unknown init {init!r}")
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


@functools.partial(jax.jit, static_argnums=(1, 2))
def _make(key: jax.Array, spec: Tuple, dtype: str) -> Any:
    leaves, treedef = spec
    keys = jax.random.split(key, len(leaves))
    return jax.tree.unflatten(
        treedef, [_init(k, s, i, dtype) for k, (s, i) in zip(keys, leaves)])


def make_params(layout: Layout, seed: int, dtype: str = "float32") -> Any:
    """The whole parameter tree of ``layout`` for a 31-bit ``seed``, in one
    jitted call."""
    leaves, treedef = jax.tree.flatten(layout, is_leaf=_is_leaf)
    spec = (tuple(leaves), treedef)
    return _make(jax.random.PRNGKey(seed), spec, dtype)
