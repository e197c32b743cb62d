"""A test architecture: the decoder with biases on its q, k and v
projections (``attention_bias``), which the system serves as
``ModelConfig.qkv_bias``.  It lives only beside the tests and edits no
file of the harness: its key table, sizes, weight layout and reference
extend the decoder's.

The biases are added to the projections before the rotary embedding, as
the system adds them; everything else is the decoder's reference.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from bench.correct import reference_decoder as dec

FIELDS = {**dec.FIELDS, "attention_bias": "qkv_bias"}
BIAS_INIT = "normal:0.5"  # large enough that a reference without them fails


dims_of = dec.dims_of


def layout(dims: Dict[str, Any]) -> Dict[str, Any]:
    tree = dec.layout(dims)
    L, D = dims["L"], dims["D"]
    tree["blocks"]["attn"].update(
        bq=((L, dims["Hq"] * D), BIAS_INIT), bk=((L, dims["Hkv"] * D), BIAS_INIT),
        bv=((L, dims["Hkv"] * D), BIAS_INIT))
    return tree


def _attention(bp, h, dims, compute):
    t = h.shape[0]
    hq, hkv, dd = dims["Hq"], dims["Hkv"], dims["D"]
    q = (dec._mm(h, bp["wq"], compute) + bp["bq"]).reshape(t, hq, dd)
    k = (dec._mm(h, bp["wk"], compute) + bp["bk"]).reshape(t, hkv, dd)
    v = (dec._mm(h, bp["wv"], compute) + bp["bv"]).reshape(t, hkv, dd)
    q, k = dec._rope(q, dims["rope_theta"]), dec._rope(k, dims["rope_theta"])
    qg = q.reshape(t, hkv, hq // hkv, dd)
    qg, k, v = dec._low(qg, compute), dec._low(k, compute), dec._low(v, compute)
    s = jnp.einsum("thgd,shd->hgts", qg, k,
                   preferred_element_type=jnp.float32) * dd ** -0.5
    causal = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    p = dec.star_softmax(s, causal, dims["int_bits"], dims["frac_bits"])
    p = dec._low(p, compute)
    o = jnp.einsum("hgts,shd->thgd", p, v, preferred_element_type=jnp.float32)
    return dec._mm(o.reshape(t, hq * dd), bp["wo"], compute)


@functools.partial(jax.jit, static_argnames=("dims_key", "compute"))
def _forward(params, tokens, *, dims_key, compute):
    dims = dict(dims_key)
    eps = dims["eps"]
    x = dec._low(params["embed"]["table"][tokens], compute)

    def layer(x, bp):
        x = x + _attention(bp["attn"], dec._rmsnorm(x, bp["ln1"]["scale"], eps),
                           dims, compute)
        h = dec._rmsnorm(x, bp["ln2"]["scale"], eps)
        m = bp["mlp"]
        return x + dec._swiglu(h, m["wi"], m["wg"], m["wo"], compute), None

    x, _ = jax.lax.scan(layer, x, params["blocks"])
    x = dec._rmsnorm(x, params["final_norm"]["scale"], eps)
    return dec._mm(x, params["unembed"]["kernel"], compute)[:, : dims["V"]]


def logits(params: Any, tokens, dims: Dict[str, Any],
           compute: Optional[str] = None) -> jax.Array:
    """``[T, vocab]`` float32 logits of a causal pass over ``tokens``."""
    with jax.default_matmul_precision("highest"):
        return _forward(params, jnp.asarray(tokens, jnp.int32),
                        dims_key=tuple(sorted(dims.items())), compute=compute)
