"""The decoder architecture that the configurations run: its key table,
sizes, weight layout and plain reference.

``FIELDS`` maps each key a configuration's ``model`` group may hold to the
system's ``ModelConfig`` field, or to a function of the ``ModelConfig``
for a value the system derives; ``dims_of`` gives the sizes the weights,
the reference and the metric readers compute with; ``layout`` the weight
tree as ``(shape, init)`` leaves (``bench/weights.py``).

The reference is straightforward ``jax.numpy`` in float32 at ``highest``
matmul precision: a full causal forward pass over one sequence, with no
cache, no kernel, no batching and no import from the system under test.  It follows the
system's stated mathematics:

* token embedding, pre-norm blocks, RMSNorm (no mean), final RMSNorm,
  untied output head over the vocabulary (padding columns dropped);
* attention with half-split rotary embedding, grouped query heads, scores
  ``q.k / sqrt(head_dim)`` and the STAR softmax: each score snaps to the
  fixed-point grid ``round(s * 2^frac)``, the row maximum is subtracted on
  that grid, the difference clips to the codebook's ``2^(int+frac)``
  levels and looks up ``exp(-k / 2^frac)``, and the row is normalized;
* SwiGLU feed-forward ``(silu(x Wg) * x Wi) Wo``;
* MoE: router logits through the same STAR softmax, the top ``K``
  experts (ties to the lower index), gates renormalized over the chosen
  ``K``, every chosen expert's SwiGLU output weighted by its gate.  No
  token is dropped: the configuration sets the capacity so that the
  system drops none either.

``compute`` lowers the precision of every matmul operand (weights and
activations) for the control: each is rounded to that type and the
product taken from the rounded values in float32, as a matmul in that
type with float32 accumulation computes it, on chips that have no such
matmul unit too.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp


def _softmax(cfg) -> Dict[str, Any]:
    sm = cfg.softmax_spec
    got = {"kind": sm.kind, "int_bits": sm.fmt.int_bits, "frac_bits": sm.fmt.frac_bits}
    if cfg.family == "moe":
        got["router"] = cfg.star_router
    return got


# model key in a configuration file -> the system's ModelConfig field, or
# a function of the ModelConfig where the system derives the value
FIELDS = {
    "num_hidden_layers": "num_layers", "hidden_size": "d_model",
    "num_attention_heads": "num_heads", "num_key_value_heads": "num_kv_heads",
    "intermediate_size": "d_ff", "vocab_size": "vocab_size",
    "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings", "num_local_experts": "num_experts",
    "num_experts_per_tok": "top_k", "capacity_factor": "capacity_factor",
    "param_dtype": "param_dtype", "compute_dtype": "compute_dtype",
    "family": "family",
    "head_dim": lambda cfg: cfg.resolved_head_dim,
    "softmax": _softmax,
}


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


def layout(dims: Dict[str, Any]) -> Dict[str, Any]:
    """The system's parameter tree: stacked pre-norm blocks with a dense
    SwiGLU FFN or routed SwiGLU experts."""
    L, d, D = dims["L"], dims["d"], dims["D"]
    hq, hkv, f = dims["Hq"] * D, dims["Hkv"] * D, dims["f"]
    mat = "fan_in"
    block: Dict[str, Any] = {
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


def _low(x, compute: Optional[str]):
    """``x`` rounded to ``compute`` and held in float32 (the control)."""
    return x if compute is None else x.astype(compute).astype(jnp.float32)


def _mm(a, b, compute: Optional[str]):
    a, b = _low(a, compute), _low(b, compute)
    return jnp.matmul(a, b, preferred_element_type=jnp.float32)


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x [T, H, D] at positions 0..T-1, half-split convention."""
    t, _, dd = x.shape
    half = dd // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs  # [T, half]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def star_softmax(s, mask, int_bits: int, frac_bits: int):
    """The STAR softmax along the last axis; masked entries get 0."""
    scale = float(1 << frac_bits)
    levels = 1 << (int_bits + frac_bits)
    j = jnp.where(mask, jnp.round(s * scale), -jnp.inf)
    k = jnp.clip(jnp.max(j, -1, keepdims=True) - j, 0.0, levels - 1.0)
    p = jnp.where(mask, jnp.exp(-k / scale), 0.0)
    return p / jnp.sum(p, -1, keepdims=True)


def _attention(bp, h, dims, compute):
    t = h.shape[0]
    hq, hkv, dd = dims["Hq"], dims["Hkv"], dims["D"]
    q = _mm(h, bp["wq"], compute).reshape(t, hq, dd)
    k = _mm(h, bp["wk"], compute).reshape(t, hkv, dd)
    v = _mm(h, bp["wv"], compute).reshape(t, hkv, dd)
    q, k = _rope(q, dims["rope_theta"]), _rope(k, dims["rope_theta"])
    qg = q.reshape(t, hkv, hq // hkv, dd)
    qg, k, v = _low(qg, compute), _low(k, compute), _low(v, compute)
    s = jnp.einsum("thgd,shd->hgts", qg, k,
                   preferred_element_type=jnp.float32) * dd ** -0.5
    causal = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    p = star_softmax(s, causal, dims["int_bits"], dims["frac_bits"])
    p = _low(p, compute)
    o = jnp.einsum("hgts,shd->thgd", p, v, preferred_element_type=jnp.float32)
    return _mm(o.reshape(t, hq * dd), bp["wo"], compute)


def _swiglu(x, wi, wg, wo, compute):
    return _mm(jax.nn.silu(_mm(x, wg, compute)) * _mm(x, wi, compute), wo, compute)


def _moe(mp, h, dims, compute):
    r = _mm(h, mp["router"], compute)  # [T, E]
    probs = star_softmax(r, jnp.ones(r.shape, bool), dims["int_bits"],
                         dims["frac_bits"])
    gates, idx = jax.lax.top_k(probs, dims["K"])  # ties: lower index first
    gates = gates / jnp.sum(gates, -1, keepdims=True)
    every = jax.vmap(lambda wi, wg, wo: _swiglu(h, wi, wg, wo, compute))(
        mp["wi"], mp["wg"], mp["wo"])  # [E, T, d]
    chosen = jnp.take_along_axis(every.transpose(1, 0, 2), idx[..., None], 1)
    return jnp.sum(chosen * gates[..., None], axis=1)


@functools.partial(jax.jit, static_argnames=("dims_key", "compute"))
def _forward(params, tokens, *, dims_key, compute):
    dims = dict(dims_key)
    eps = dims["eps"]
    x = params["embed"]["table"][tokens]
    x = _low(x, compute)

    def layer(x, bp):
        x = x + _attention(bp["attn"], _rmsnorm(x, bp["ln1"]["scale"], eps),
                           dims, compute)
        h = _rmsnorm(x, bp["ln2"]["scale"], eps)
        if dims["family"] == "moe":
            return x + _moe(bp["moe"], h, dims, compute), None
        m = bp["mlp"]
        return x + _swiglu(h, m["wi"], m["wg"], m["wo"], compute), None

    x, _ = jax.lax.scan(layer, x, params["blocks"])  # one layer at a time
    x = _rmsnorm(x, params["final_norm"]["scale"], eps)
    return _mm(x, params["unembed"]["kernel"], compute)[:, : dims["V"]]


def logits(params: Any, tokens, dims: Dict[str, Any],
           compute: Optional[str] = None) -> jax.Array:
    """``[T, vocab]`` float32 logits of a causal pass over ``tokens``;
    row ``p`` predicts token ``p + 1``."""
    with jax.default_matmul_precision("highest"):
        return _forward(params, jnp.asarray(tokens, jnp.int32),
                        dims_key=tuple(sorted(dims.items())), compute=compute)
