"""Shared model building blocks (functional style: spec_* declares params,
apply-style functions consume them).  All attention flows through the STAR
softmax engine unless the config says otherwise.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

import dataclasses

from repro import ops
from repro.configs.base import ModelConfig
from repro.core import kvquant
from repro.distributed.sharding import with_logical_constraint as wlc
from repro.models.param import ParamSpec

Params = Dict[str, Any]


def cdtype(cfg: ModelConfig):
    return jnp.dtype(cfg.compute_dtype)


def pdtype(cfg: ModelConfig):
    return jnp.dtype(cfg.param_dtype)


# ---------------------------------------------------------------------------
# Norms


def spec_rmsnorm(cfg: ModelConfig, dim: Optional[int] = None) -> Params:
    return {"scale": ParamSpec((dim or cfg.d_model,), ("embed",), pdtype(cfg), "ones")}


def rmsnorm(p: Params, x: jax.Array, eps: float) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps) * p["scale"].astype(jnp.float32)
    return out.astype(x.dtype)


def spec_layernorm(cfg: ModelConfig, dim: Optional[int] = None) -> Params:
    d = dim or cfg.d_model
    return {
        "scale": ParamSpec((d,), ("embed",), pdtype(cfg), "ones"),
        "bias": ParamSpec((d,), ("embed",), pdtype(cfg), "zeros"),
    }


def layernorm(p: Params, x: jax.Array, eps: float) -> jax.Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    out = (xf - mu) * jax.lax.rsqrt(var + eps)
    out = out * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Embedding / unembedding


def spec_embedding(cfg: ModelConfig) -> Params:
    return {
        "table": ParamSpec(
            (cfg.padded_vocab, cfg.d_model), ("vocab", "embed"), pdtype(cfg), "embed"
        )
    }


def embed(p: Params, tokens: jax.Array, cfg: ModelConfig) -> jax.Array:
    out = jnp.take(p["table"].astype(cdtype(cfg)), tokens, axis=0)
    return wlc(out, ("batch", "seq", "embed"))


def spec_unembed(cfg: ModelConfig) -> Params:
    if cfg.tie_embeddings:
        return {}
    return {
        "kernel": ParamSpec(
            (cfg.d_model, cfg.padded_vocab), ("embed", "vocab"), pdtype(cfg), "fan_in"
        )
    }


def unembed(p: Params, x: jax.Array, cfg: ModelConfig, embed_params: Params) -> jax.Array:
    if cfg.tie_embeddings:
        kernel = embed_params["table"].astype(cdtype(cfg)).T
    else:
        kernel = p["kernel"].astype(cdtype(cfg))
    logits = jnp.einsum("...d,dv->...v", x, kernel)
    if cfg.padded_vocab != cfg.vocab_size:  # mask padding columns
        valid = jnp.arange(cfg.padded_vocab) < cfg.vocab_size
        logits = jnp.where(valid, logits, jnp.asarray(-1e30, logits.dtype))
    return wlc(logits, ("batch", "seq", "vocab"))


# ---------------------------------------------------------------------------
# RoPE (standard + M-RoPE)


def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x [B, T, H, D] rotated by positions [B, T] (half-split convention)."""
    half = x.shape[-1] // 2
    freqs = rope_freqs(x.shape[-1], theta)  # [half]
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B, T, half]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    return jnp.concatenate(
        [xf1 * cos - xf2 * sin, xf2 * cos + xf1 * sin], axis=-1
    ).astype(x.dtype)


def apply_mrope(
    x: jax.Array, positions: jax.Array, theta: float, sections: Tuple[int, ...]
) -> jax.Array:
    """Multimodal RoPE (Qwen2-VL): positions [B, T, 3] = (t, h, w) ids;
    ``sections`` splits the half-dim into per-stream frequency bands."""
    import numpy as np

    half = x.shape[-1] // 2
    assert sum(sections) == half, (sections, half)
    freqs = rope_freqs(x.shape[-1], theta)  # [half]
    # which positional stream (t/h/w) drives each frequency band — static
    stream = jnp.asarray(np.repeat(np.arange(len(sections)), sections))  # [half]
    pos = jnp.take(positions.astype(jnp.float32), stream, axis=-1)  # [B, T, half]
    angles = pos * freqs
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    return jnp.concatenate(
        [xf1 * cos - xf2 * sin, xf2 * cos + xf1 * sin], axis=-1
    ).astype(x.dtype)


def sinusoidal_positions(t0: int | jax.Array, length: int, d_model: int) -> jax.Array:
    """Classic sinusoidal table slice [length, d_model] (seamless enc-dec)."""
    pos = (jnp.arange(length) + t0)[:, None].astype(jnp.float32)
    half = d_model // 2
    div = jnp.exp(-jnp.arange(half, dtype=jnp.float32) * (jnp.log(10000.0) / half))
    ang = pos * div[None, :]
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ---------------------------------------------------------------------------
# Attention block


def spec_attention(cfg: ModelConfig, cross: bool = False) -> Params:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    hq, hkv = cfg.num_heads, cfg.num_kv_heads
    pd = pdtype(cfg)
    p: Params = {
        "wq": ParamSpec((d, hq * hd), ("embed", "heads"), pd, "fan_in"),
        "wk": ParamSpec((d, hkv * hd), ("embed", "kv_heads"), pd, "fan_in"),
        "wv": ParamSpec((d, hkv * hd), ("embed", "kv_heads"), pd, "fan_in"),
        "wo": ParamSpec((hq * hd, d), ("heads", "embed"), pd, "fan_in"),
    }
    if cfg.qkv_bias:
        p["bq"] = ParamSpec((hq * hd,), ("heads",), pd, "zeros")
        p["bk"] = ParamSpec((hkv * hd,), ("kv_heads",), pd, "zeros")
        p["bv"] = ParamSpec((hkv * hd,), ("kv_heads",), pd, "zeros")
    return p


def _project_qkv(p: Params, x: jax.Array, xkv: jax.Array, cfg: ModelConfig):
    dt = cdtype(cfg)
    hd = cfg.resolved_head_dim
    q = jnp.einsum("btd,dh->bth", x, p["wq"].astype(dt))
    k = jnp.einsum("btd,dh->bth", xkv, p["wk"].astype(dt))
    v = jnp.einsum("btd,dh->bth", xkv, p["wv"].astype(dt))
    if "bq" in p:
        q = q + p["bq"].astype(dt)
        k = k + p["bk"].astype(dt)
        v = v + p["bv"].astype(dt)
    b, tq = q.shape[0], q.shape[1]
    tk = k.shape[1]
    q = q.reshape(b, tq, cfg.num_heads, hd)
    k = k.reshape(b, tk, cfg.num_kv_heads, hd)
    v = v.reshape(b, tk, cfg.num_kv_heads, hd)
    return q, k, v


def attention_block(
    p: Params,
    x: jax.Array,
    cfg: ModelConfig,
    *,
    causal: bool = True,
    positions: Optional[jax.Array] = None,  # [B, T] or [B, T, 3] for M-RoPE
    sliding_window: Optional[int] = None,
    cache: Optional[Params] = None,  # {"k","v","len"} decode cache
    xkv: Optional[jax.Array] = None,  # cross-attention memory
    kv_valid_len: Optional[jax.Array] = None,
    use_rope: bool = True,
    paged_cache_t: Optional[int] = None,  # paged cache: logical row count
) -> Tuple[jax.Array, Optional[Params], Tuple[jax.Array, jax.Array]]:
    """Self- or cross-attention with optional KV cache.

    The cache comes in three shapes: a scalar-``len`` decode cache, a
    per-slot pool (``len`` is a ``[B]`` vector), and a *paged* pool —
    K/V are every layer's ``[L, num_blocks, block_size, Hkv, D]`` page
    pools, a ``"layer"`` index naming this layer's slab, and a
    ``"tables"`` entry of per-slot block tables (``repro.serve.paged``),
    with ``paged_cache_t`` carrying the logical per-slot row count (a
    static int: it sizes the gathered view and the ring modulo).  The
    whole pool travels so the one-row writes update it in place; a
    per-layer slice would copy the layer's pages.

    Returns ``(out, cache', (k, v))`` — the fresh (rotated) K/V of this call
    so prefill can prime caches without recomputing projections."""
    b, tq, _ = x.shape
    q, k, v = _project_qkv(p, x, x if xkv is None else xkv, cfg)

    if use_rope and xkv is None:
        if positions is None:
            base = cache["len"] if cache is not None else 0
            if jnp.ndim(base) == 1:
                # the pool's rope counters live in the model-level cache
                # ("pos", which diverges from "len" for VLM); this layer
                # cannot reconstruct them from "len" alone
                raise ValueError(
                    "per-slot caches require explicit positions "
                    "(decode_step builds them from the pool's 'pos' counters)"
                )
            positions = base + jnp.arange(tq)[None, :].astype(jnp.int32)
            positions = jnp.broadcast_to(positions, (b, tq))
        if cfg.mrope_sections and positions.ndim == 3:
            q = apply_mrope(q, positions, cfg.rope_theta, cfg.mrope_sections)
            k = apply_mrope(k, positions, cfg.rope_theta, cfg.mrope_sections)
        else:
            if positions.ndim == 3:
                positions = positions[..., 0]
            q = apply_rope(q, positions, cfg.rope_theta)
            k = apply_rope(k, positions, cfg.rope_theta)

    if cfg.seq_parallel_activations and tq > 1:
        # heads that don't divide the model axis (e.g. 28, 56) leave the
        # score tensor replicated and XLA all-reduces partial products per
        # KV block; sharding the q/score ROWS over the model axis instead
        # keeps softmax row-local (§Perf prefill finding)
        q = wlc(q, ("batch", "act_seq", "heads", None))
    else:
        q = wlc(q, ("batch", "seq", "heads", None))
    q_offset: jax.Array | int = 0
    new_cache = None
    if cache is not None and "tables" in cache:
        # Paged slot pool (DESIGN.md §8): K/V live in a flat block pool,
        # per-slot block tables give each slot a ragged logical buffer.
        # Same contract as the dense per-slot path below — write the fresh
        # token at the slot's own depth, mask by per-slot valid length —
        # but the write is a block-indirected scatter and the read is a
        # table gather inside the paged_attention op.
        assert tq == 1, "paged cache only supports 1-token decode"
        assert paged_cache_t is not None, "paged cache requires paged_cache_t"
        cache_t = paged_cache_t
        bs = cache["k"].shape[2]
        tables = cache["tables"]
        layer = cache["layer"]
        ring = sliding_window is not None and cache_t <= sliding_window
        idx = cache["len"] % cache_t if ring else cache["len"]
        # free slots' counters regrow past their (scratch-only) tables; the
        # clip keeps the gather in range, their writes land in scratch
        col = jnp.clip(idx // bs, 0, tables.shape[1] - 1)
        blk = jnp.take_along_axis(tables, col[:, None], axis=1)[:, 0]
        row = idx % bs
        new_len = cache["len"] + 1
        kv_dtype = kvquant.dtype_of(cache["k"].dtype)
        if kv_dtype != "fp32":
            # Quantized pool (DESIGN.md §13): scatter *codes*, and stamp
            # the block's scale row only on the block's first write — later
            # rows reuse the stamp (clipped encode), so a block's codes
            # always decode through the scale they were written with.  On a
            # ring's second lap (len >= cache_t) the previous lap's rows
            # still decode through the existing stamp, so wrap never
            # restamps.
            krow = k[:, 0].astype(jnp.float32)  # [S, Hkv, D]
            vrow = v[:, 0].astype(jnp.float32)
            fresh = row == 0
            if ring:
                fresh = fresh & (cache["len"] < cache_t)
            k_sc = jnp.where(
                fresh[:, None],
                kvquant.row_scale(krow, kv_dtype),
                cache["k_scale"][layer, blk],
            )
            v_sc = jnp.where(
                fresh[:, None],
                kvquant.row_scale(vrow, kv_dtype),
                cache["v_scale"][layer, blk],
            )
            ck = cache["k"].at[layer, blk, row].set(
                kvquant.encode(krow, k_sc[..., None], kv_dtype)
            )
            cv = cache["v"].at[layer, blk, row].set(
                kvquant.encode(vrow, v_sc[..., None], kv_dtype)
            )
            ks_pages = cache["k_scale"].at[layer, blk].set(k_sc)
            vs_pages = cache["v_scale"].at[layer, blk].set(v_sc)
            new_cache = {
                "k": ck, "v": cv,
                "k_scale": ks_pages, "v_scale": vs_pages,
                "len": new_len,
            }
            kv_scales = (ks_pages, vs_pages)
        else:
            ck = cache["k"].at[layer, blk, row].set(
                k[:, 0].astype(cache["k"].dtype))
            cv = cache["v"].at[layer, blk, row].set(
                v[:, 0].astype(cache["v"].dtype))
            new_cache = {"k": ck, "v": cv, "len": new_len}
            kv_scales = None
        kvl = jnp.minimum(new_len, cache_t) if ring else new_len
        spec = dataclasses.replace(
            cfg.paged_attention_spec, block_size=bs, kv_dtype=kv_dtype
        )
        ctx = ops.paged_attention(
            q, ck, cv, tables, spec, kv_valid_len=kvl, kv_len=cache_t,
            kv_scales=kv_scales, layer=layer,
        )
        return ctx.reshape(b, tq, -1), new_cache, (k, v)
    if cache is not None:
        cache_t = cache["k"].shape[1]
        # Per-slot serving pool: cache["len"] is a [B] vector — every slot
        # decodes at its own depth, so the write index and the valid-length
        # mask are per batch row (continuous batching, DESIGN.md §6).
        per_slot = jnp.ndim(cache["len"]) == 1
        ring = sliding_window is not None and cache_t <= sliding_window
        if per_slot:
            assert tq == 1, "per-slot cache only supports 1-token decode"
            idx = cache["len"] % cache_t if ring else cache["len"]
            # blend-style write: dynamic_update_slice cannot take a
            # per-batch index, the one-hot hit mask can
            hit = (jnp.arange(cache_t)[None, :] == idx[:, None])[..., None, None]
            ck = jnp.where(hit, k.astype(cache["k"].dtype), cache["k"])
            cv = jnp.where(hit, v.astype(cache["v"].dtype), cache["v"])
            k_full, v_full = ck, cv
            window_decode = ring
        elif ring:
            # ring-buffer for sliding windows, append otherwise
            assert tq == 1, "ring-buffer window cache only supports 1-token decode"
            idx = cache["len"] % cache_t
            ck = jax.lax.dynamic_update_slice(cache["k"], k, (0, idx, 0, 0))
            cv = jax.lax.dynamic_update_slice(cache["v"], v, (0, idx, 0, 0))
            # positions of ring slots are implicit; use unrotated ring order
            # only when tq == 1 (decode), which is the serving path.
            k_full, v_full = ck, cv
            window_decode = True
        elif cfg.kv_update == "onehot" and tq == 1:
            # sharding-friendly append: elementwise blend, no cross-shard
            # dynamic update (see ModelConfig.kv_update)
            hit = (jnp.arange(cache_t) == cache["len"])[None, :, None, None]
            ck = jnp.where(hit, k.astype(cache["k"].dtype), cache["k"])
            cv = jnp.where(hit, v.astype(cache["v"].dtype), cache["v"])
            k_full, v_full = ck, cv
            window_decode = False
        else:
            ck = jax.lax.dynamic_update_slice(cache["k"], k, (0, cache["len"], 0, 0))
            cv = jax.lax.dynamic_update_slice(cache["v"], v, (0, cache["len"], 0, 0))
            k_full, v_full = ck, cv
            window_decode = False
        new_len = cache["len"] + tq
        new_cache = {"k": ck, "v": cv, "len": new_len}
        k_full = wlc(k_full, ("batch", "kv_seq", "kv_heads", None))
        v_full = wlc(v_full, ("batch", "kv_seq", "kv_heads", None))
        if window_decode or per_slot:
            # Single-token decode: the causal constraint is exactly "attend
            # to the first new_len cache rows", so a (per-batch) valid-length
            # mask subsumes it.  Ring caches additionally clamp to the window
            # capacity — slots >= len are zeros until the ring wraps.
            kvl = jnp.minimum(new_len, cache_t) if window_decode else new_len
            kvl = jnp.broadcast_to(kvl, (b,))
            out = _run_attention(
                q, k_full, v_full, cfg,
                causal=False, sliding_window=None, q_offset=0,
                kv_valid_len=kvl,
            )
            return out, new_cache, (k, v)
        q_offset = cache["len"]
        fresh_k, fresh_v = k, v
        k, v = k_full, v_full
        kv_valid_len = jnp.broadcast_to(new_len, (b,))
    else:
        fresh_k, fresh_v = k, v

    out = _run_attention(
        q, k, v, cfg,
        causal=causal and xkv is None,
        sliding_window=sliding_window,
        q_offset=q_offset,
        kv_valid_len=kv_valid_len,
    )
    return out, new_cache, (fresh_k, fresh_v)


def _run_attention(
    q, k, v, cfg: ModelConfig, *, causal, sliding_window, q_offset, kv_valid_len
) -> jax.Array:
    # One dispatch for every backend (repro.ops): the config carries the
    # static contract (impl, softmax engine, blocking), the call site only
    # supplies the per-invocation masking.  Decode-vs-prefill selection
    # (scan blocks only for long prefill rows — the §Perf decode finding)
    # lives inside the "xla" backend.
    ctx = ops.attention(
        q, k, v, cfg.attention_spec,
        causal=causal,
        sliding_window=sliding_window,
        q_offset=q_offset,
        kv_valid_len=kv_valid_len,
    )
    b, tq = ctx.shape[0], ctx.shape[1]
    return ctx.reshape(b, tq, -1)


def attention_out(p: Params, ctx: jax.Array, cfg: ModelConfig) -> jax.Array:
    out = jnp.einsum("bth,hd->btd", ctx, p["wo"].astype(cdtype(cfg)))
    return wlc(out, ("batch", "seq", "embed"))


# ---------------------------------------------------------------------------
# MLP


def spec_mlp(cfg: ModelConfig, d_ff: Optional[int] = None) -> Params:
    d, f = cfg.d_model, d_ff or cfg.d_ff
    pd = pdtype(cfg)
    if cfg.mlp_type == "swiglu":
        return {
            "wi": ParamSpec((d, f), ("embed", "mlp"), pd, "fan_in"),
            "wg": ParamSpec((d, f), ("embed", "mlp"), pd, "fan_in"),
            "wo": ParamSpec((f, d), ("mlp", "embed"), pd, "fan_in"),
        }
    return {
        "wi": ParamSpec((d, f), ("embed", "mlp"), pd, "fan_in"),
        "wo": ParamSpec((f, d), ("mlp", "embed"), pd, "fan_in"),
    }


def mlp(p: Params, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    dt = cdtype(cfg)
    h = jnp.einsum("btd,df->btf", x, p["wi"].astype(dt))
    if cfg.mlp_type == "swiglu":
        g = jnp.einsum("btd,df->btf", x, p["wg"].astype(dt))
        h = jax.nn.silu(g) * h
    else:
        h = jax.nn.gelu(h)
    h = wlc(h, ("batch", "seq", "mlp"))
    out = jnp.einsum("btf,fd->btd", h, p["wo"].astype(dt))
    return wlc(out, ("batch", "seq", "embed"))


# ---------------------------------------------------------------------------
# MoE (granite-moe: EP over 32 experts; mixtral: TP over 8 experts)


def spec_moe(cfg: ModelConfig) -> Params:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.num_experts
    pd = pdtype(cfg)
    return {
        "router": ParamSpec((d, e), ("embed", None), pd, "fan_in"),
        "wi": ParamSpec((e, d, f), ("expert", "embed", "mlp"), pd, "fan_in"),
        "wg": ParamSpec((e, d, f), ("expert", "embed", "mlp"), pd, "fan_in"),
        "wo": ParamSpec((e, f, d), ("expert", "mlp", "embed"), pd, "fan_in"),
    }


def moe_capacity(cfg: ModelConfig, tokens_per_group: int) -> int:
    """Per-expert queue capacity for a ``tokens_per_group``-token call.

    Factored out because capacity is *shape-dependent*: chunked prefill must
    pass the capacity of the **full** sequence into every chunk (plus the
    carried queue counts, see ``moe(state=...)``) or token-dropping decisions
    — and therefore the outputs — would differ from a monolithic prefill.
    """
    return max(1, int(cfg.capacity_factor * cfg.top_k * tokens_per_group / cfg.num_experts))


def moe(
    p: Params,
    x: jax.Array,
    cfg: ModelConfig,
    *,
    state: Optional[jax.Array] = None,
    capacity: Optional[int] = None,
):
    """Grouped one-hot dispatch MoE (GShard-style, capacity-dropped).

    The router softmax runs through the STAR engine when cfg.star_router —
    the paper's point (softmax precision-insensitivity) applies to routing
    distributions at least as well as to attention.

    ``state`` / ``capacity`` make the capacity-dropping decision
    *chunk-invariant* for chunked prefill: ``state`` ([groups, experts]
    int32) carries per-expert assignment counts from earlier chunks of the
    same sequence (so queue positions are global, not per-call), and
    ``capacity`` overrides the per-call queue bound with the full-sequence
    one.  When either is given the call returns ``(y, new_state)``; the
    bare-``y`` legacy form (both None) is bit-identical to the historical
    behavior.  Every (token, choice) occupies its *global* queue position,
    so chunk-wise outputs match the monolithic pass exactly: the expert FFN
    is row-independent and the combine weights select identical rows.
    """
    dt = cdtype(cfg)
    b, t, d = x.shape
    e, k = cfg.num_experts, cfg.top_k
    tokens = b * t
    groups = b  # one group per batch row keeps dispatch O(T^2/G) local
    tg = tokens // groups
    xg = x.reshape(groups, tg, d)
    stateful = state is not None or capacity is not None

    logits = jnp.einsum("gtd,de->gte", xg, p["router"].astype(dt)).astype(jnp.float32)
    spec = cfg.softmax_spec
    if not cfg.star_router:
        spec = dataclasses.replace(spec, kind="exact")
    if spec.kind == "exact":
        # exact routing distribution: the pallas engine is star-only, so
        # route the oracle through reference rather than a capability error
        spec = dataclasses.replace(spec, impl="reference")
    probs = ops.softmax(logits, spec)

    gate_vals, gate_idx = jax.lax.top_k(probs, k)  # [g, t, k]
    gate_vals = gate_vals / jnp.maximum(jnp.sum(gate_vals, -1, keepdims=True), 1e-9)

    cap = capacity if capacity is not None else moe_capacity(cfg, tg)
    # position of each (token, choice) within its expert queue
    onehot = jax.nn.one_hot(gate_idx, e, dtype=jnp.float32)  # [g, t, k, e]
    flat = onehot.reshape(groups, tg * k, e)
    pos = (jnp.cumsum(flat, axis=1) - flat).reshape(groups, tg, k, e)
    if state is not None:
        # offset intra-call positions by the prior chunks' per-expert
        # counts so position == global queue position for this sequence
        pos = pos + state.astype(jnp.float32)[:, None, None, :]
    pos = jnp.sum(pos * onehot, axis=-1)  # [g, t, k]
    keep = pos < cap
    gate_vals = gate_vals * keep

    # dispatch [g, t, e, cap] combine weights
    pos_oh = jax.nn.one_hot(pos.astype(jnp.int32), cap, dtype=jnp.float32)  # [g,t,k,cap]
    dispatch = jnp.einsum("gtke,gtkc->gtec", onehot * keep[..., None], pos_oh)
    combine = jnp.einsum("gtke,gtkc,gtk->gtec", onehot, pos_oh, gate_vals)

    xin = jnp.einsum("gtec,gtd->egcd", dispatch, xg.astype(jnp.float32)).astype(dt)
    xin = wlc(xin, ("expert", "batch", None, "embed"))
    h = jnp.einsum("egcd,edf->egcf", xin, p["wi"].astype(dt))
    g_ = jnp.einsum("egcd,edf->egcf", xin, p["wg"].astype(dt))
    h = jax.nn.silu(g_) * h
    h = wlc(h, ("expert", "batch", None, "mlp"))
    out = jnp.einsum("egcf,efd->egcd", h, p["wo"].astype(dt))
    out = wlc(out, ("expert", "batch", None, "embed"))
    y = jnp.einsum("gtec,egcd->gtd", combine.astype(dt), out)
    y = wlc(y.reshape(b, t, d), ("batch", "seq", "embed"))
    if not stateful:
        return y
    # counts include dropped choices — the monolithic cumsum does too
    counts = jnp.sum(onehot, axis=(1, 2)).astype(jnp.int32)  # [g, e]
    new_state = counts if state is None else state + counts
    return y, new_state


def scan_blocks(body, carry, xs, use_scan: bool = True):
    """lax.scan over stacked block params; unrolls under the dry-run cost
    probe context (see core.scan_ctl) or when use_scan=False."""
    from repro.core.scan_ctl import scan_or_unroll, unroll_scans_enabled

    if use_scan and not unroll_scans_enabled():
        return jax.lax.scan(body, carry, xs)
    n = jax.tree.leaves(xs)[0].shape[0]
    ys = []
    for i in range(n):
        carry, y = body(carry, jax.tree.map(lambda a: a[i], xs))
        ys.append(y)
    if ys and ys[0] is not None:
        ys = jax.tree.map(lambda *zs: jnp.stack(zs), *ys)
    else:
        ys = None
    return carry, ys


def fit_window_cache(k: jax.Array, v: jax.Array, seq_axis: int, wlen: int, seq_len: int):
    """Trim prefill K/V to a ``wlen`` ring cache with slot = position % wlen.

    Decode inserts at ``len % wlen``, so the kept window must be *rolled* so
    token ``j`` sits at slot ``j % wlen`` — a plain "keep last wlen" layout
    would be overwritten in the wrong order.
    """
    seq = k.shape[seq_axis]
    assert seq == seq_len
    if seq >= wlen:
        sl = [slice(None)] * k.ndim
        sl[seq_axis] = slice(seq - wlen, seq)
        kk, vv = k[tuple(sl)], v[tuple(sl)]
        shift = (seq_len - wlen) % wlen
        kk = jnp.roll(kk, shift, axis=seq_axis)
        vv = jnp.roll(vv, shift, axis=seq_axis)
        return kk, vv
    pad = [(0, 0)] * k.ndim
    pad[seq_axis] = (0, wlen - seq)
    return jnp.pad(k, pad), jnp.pad(v, pad)


# ---------------------------------------------------------------------------
# Causal depthwise conv (mamba2 / recurrentgemma)


def spec_conv1d(cfg: ModelConfig, channels: int, width: int) -> Params:
    return {"kernel": ParamSpec((width, channels), ("conv", "mlp"), pdtype(cfg), "fan_in")}


def causal_conv1d(
    p: Params, x: jax.Array, state: Optional[jax.Array] = None
) -> Tuple[jax.Array, Optional[jax.Array]]:
    """Depthwise causal conv.  x [B, T, C]; state [B, W-1, C] carries context
    for decode.  Returns (y, new_state)."""
    w = p["kernel"].astype(x.dtype)  # [W, C]
    width = w.shape[0]
    if state is None:
        xp = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
        new_state = None
    else:
        xp = jnp.concatenate([state.astype(x.dtype), x], axis=1)
        new_state = xp[:, -(width - 1):, :]
    y = sum(
        xp[:, i : xp.shape[1] - (width - 1 - i), :] * w[i]
        for i in range(width)
    )
    return y, new_state
