"""Logical-axis sharding rules (MaxText-style) for the production mesh.

Models annotate parameters/activations with *logical* axes ("embed", "mlp",
"heads", "vocab", "expert", "batch", ...).  A rule table maps logical axes
to mesh axes; :func:`logical_to_pspec` resolves them with two safety rails:

  * **divisibility auto-drop** — a logical axis whose dim is not divisible
    by the mapped mesh axes is left unsharded (e.g. 8 KV heads on a
    16-way model axis degrade to replicated KV, exactly what you want);
  * **single-use** — a mesh axis may appear once per PartitionSpec; later
    dims drop it (e.g. EP expert dim + TP mlp dim both wanting "model").

``use_mesh_rules`` installs an ambient (mesh, rules) context so layer code
can call :func:`with_logical_constraint` without threading the mesh through
every function — outside the context it is an identity, which is what smoke
tests on one device want.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models.param import ParamSpec, axes_tree

# Rule value: a mesh axis name, a tuple of mesh axis names, or None.
Rules = Dict[str, Any]

# Default rules for FSDP x TP on ("pod", "data", "model").  "pod" acts as an
# outer data axis; missing mesh axes are skipped so the same table serves
# single-pod and multi-pod meshes.
DEFAULT_RULES: Rules = {
    "batch": ("pod", "data"),
    "embed": ("data",),  # FSDP: weights sharded along embed over data
    "mlp": ("model",),
    "heads": ("model",),
    "kv_heads": ("model",),
    "vocab": ("model",),
    "expert": ("model",),
    "qkv": ("model",),
    "kv_seq": ("model",),  # decode-time KV cache sequence sharding (SP)
    "act_seq": ("model",),  # inter-block activation sequence parallelism
    "seq": (),
    "layers": (),
    "state": (),
    "conv": (),
}


def make_rules(**overrides: Any) -> Rules:
    rules = dict(DEFAULT_RULES)
    for k, v in overrides.items():
        if v is None:
            rules[k] = ()
        elif isinstance(v, str):
            rules[k] = (v,)
        else:
            rules[k] = tuple(v)
    return rules


def _normalize(rule: Any) -> Tuple[str, ...]:
    if rule is None:
        return ()
    if isinstance(rule, str):
        return (rule,)
    return tuple(rule)


def logical_to_pspec(
    axes: Sequence[Optional[str]],
    shape: Sequence[int],
    rules: Rules,
    mesh: Mesh,
) -> P:
    """Resolve logical axes to a PartitionSpec on ``mesh``."""
    used: set = set()
    entries = []
    for dim, name in zip(shape, axes):
        mesh_axes: Tuple[str, ...] = ()
        if name is not None:
            cand = [
                a
                for a in _normalize(rules.get(name, ()))
                if a in mesh.shape and a not in used
            ]
            # greedy prefix whose product divides the dim
            chosen = []
            prod = 1
            for a in cand:
                if dim % (prod * mesh.shape[a]) == 0:
                    chosen.append(a)
                    prod *= mesh.shape[a]
            mesh_axes = tuple(chosen)
            used.update(mesh_axes)
        if len(mesh_axes) == 0:
            entries.append(None)
        elif len(mesh_axes) == 1:
            entries.append(mesh_axes[0])
        else:
            entries.append(mesh_axes)
    return P(*entries)


def param_pspecs(specs_tree: Any, rules: Rules, mesh: Mesh) -> Any:
    """Tree of PartitionSpec matching a tree of ParamSpec."""
    return jax.tree.map(
        lambda s: logical_to_pspec(s.axes, s.shape, rules, mesh),
        specs_tree,
        is_leaf=lambda x: isinstance(x, ParamSpec),
    )


def param_shardings(specs_tree: Any, rules: Rules, mesh: Mesh) -> Any:
    return jax.tree.map(
        lambda s: NamedSharding(mesh, logical_to_pspec(s.axes, s.shape, rules, mesh)),
        specs_tree,
        is_leaf=lambda x: isinstance(x, ParamSpec),
    )


# ---------------------------------------------------------------------------
# Ambient mesh/rules context for activation constraints inside model code.

_ctx = threading.local()


@contextlib.contextmanager
def use_mesh_rules(mesh: Optional[Mesh], rules: Optional[Rules] = None):
    prev = getattr(_ctx, "state", None)
    _ctx.state = (mesh, rules or DEFAULT_RULES) if mesh is not None else None
    try:
        yield
    finally:
        _ctx.state = prev


def current_mesh_rules():
    return getattr(_ctx, "state", None)


def mesh_rules_key():
    """The ambient (mesh, rules) as a hashable key, rules as
    ``logical_to_pspec`` reads them: a jit cache keyed by it traces anew
    under another mesh context."""
    state = current_mesh_rules()
    if state is None:
        return None
    mesh, rules = state
    return mesh, tuple(sorted((k, _normalize(v)) for k, v in rules.items()))


def with_logical_constraint(x: jax.Array, axes: Sequence[Optional[str]]) -> jax.Array:
    """Apply a sharding constraint if a mesh context is active, else no-op."""
    state = current_mesh_rules()
    if state is None:
        return x
    mesh, rules = state
    spec = logical_to_pspec(axes, x.shape, rules, mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def bytes_per_device(specs_tree: Any, rules: Rules, mesh: Mesh) -> int:
    """Parameter bytes resident per device under the rules (napkin math)."""
    total = 0
    leaves = jax.tree.leaves(specs_tree, is_leaf=lambda x: isinstance(x, ParamSpec))
    for s in leaves:
        pspec = logical_to_pspec(s.axes, s.shape, rules, mesh)
        shards = 1
        for entry in pspec:
            if entry is None:
                continue
            for a in _normalize(entry):
                shards *= mesh.shape[a]
        total += int(np.prod(s.shape)) * np.dtype(s.dtype).itemsize // max(shards, 1)
    return total
