"""Seeded random weights, made on the device in one jitted call.

The layout is the system's parameter tree (checked against it in
``run.py``); the values are the benchmark's own: every matrix
N(0, 1/fan_in) over its input width, the embedding N(0, 0.02^2), every
norm scale 1.  The plain reference reads the same arrays, so neither side
takes anything the program made.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

Layout = Dict[str, Any]  # nested dict of (shape, init) leaves


def dims_of(model: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes the benchmark computes with, from a configuration file's
    ``model`` group (Hugging Face key names)."""
    vocab = model["vocab_size"]
    return {
        "family": model["family"],
        "L": model["num_hidden_layers"],
        "d": model["hidden_size"],
        "Hq": model["num_attention_heads"],
        "Hkv": model["num_key_value_heads"],
        "D": model["head_dim"],
        "f": model["intermediate_size"],
        "V": vocab,
        "Vp": -(-vocab // 512) * 512,  # the system pads the vocabulary to 512
        "E": model.get("num_local_experts", 0),
        "K": model.get("num_experts_per_tok", 0),
        "rope_theta": model["rope_theta"],
        "eps": model["rms_norm_eps"],
        "int_bits": model["softmax"]["int_bits"],
        "frac_bits": model["softmax"]["frac_bits"],
    }


def layout(dims: Dict[str, Any]) -> Layout:
    L, d, D = dims["L"], dims["d"], dims["D"]
    hq, hkv, f = dims["Hq"] * D, dims["Hkv"] * D, dims["f"]
    mat = "fan_in"
    block: Layout = {
        "ln1": {"scale": ((L, d), "ones")},
        "attn": {
            "wq": ((L, d, hq), mat), "wk": ((L, d, hkv), mat),
            "wv": ((L, d, hkv), mat), "wo": ((L, hq, d), mat),
        },
        "ln2": {"scale": ((L, d), "ones")},
    }
    if dims["family"] == "moe":
        e = dims["E"]
        block["moe"] = {
            "router": ((L, d, e), mat), "wi": ((L, e, d, f), mat),
            "wg": ((L, e, d, f), mat), "wo": ((L, e, f, d), mat),
        }
    else:
        block["mlp"] = {"wi": ((L, d, f), mat), "wg": ((L, d, f), mat),
                        "wo": ((L, f, d), mat)}
    return {
        "embed": {"table": ((dims["Vp"], d), "embed")},
        "blocks": block,
        "final_norm": {"scale": ((d,), "ones")},
        "unembed": {"kernel": ((d, dims["Vp"]), mat)},
    }


def _is_leaf(x) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[1], str)


def shapes(dims: Dict[str, Any]) -> Any:
    return jax.tree.map(lambda leaf: leaf[0], layout(dims), is_leaf=_is_leaf)


def _init(key: jax.Array, shape: Tuple[int, ...], init: str, dtype) -> jax.Array:
    if init == "ones":
        return jnp.ones(shape, dtype)
    std = 0.02 if init == "embed" else shape[-2] ** -0.5  # fan_in: input width
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


@functools.partial(jax.jit, static_argnums=(1, 2))
def _make(key: jax.Array, spec: Tuple, dtype: str) -> Any:
    leaves, treedef = spec
    keys = jax.random.split(key, len(leaves))
    return jax.tree.unflatten(
        treedef, [_init(k, s, i, dtype) for k, (s, i) in zip(keys, leaves)])


def make_params(dims: Dict[str, Any], seed: int, dtype: str = "float32") -> Any:
    """The whole parameter tree for a 31-bit ``seed``, in one jitted call."""
    leaves, treedef = jax.tree.flatten(layout(dims), is_leaf=_is_leaf)
    spec = (tuple(leaves), treedef)
    return _make(jax.random.PRNGKey(seed), spec, dtype)
