"""Decoder-only transformer LM: dense, MoE, and VLM variants.

One definition serves granite-8b, qwen2-72b, deepseek-coder-33b,
llama3-405b (dense), granite-moe / mixtral (MoE), and qwen2-vl (VLM
backbone with stub patch embeddings + M-RoPE).

Layers are scan-stacked (``cfg.scan_layers``) so XLA compiles ONE block and
loops it — essential for the 512-device dry-runs — with per-block remat.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import kvquant
from repro.core.scan_ctl import unroll_scans_enabled
from repro.distributed.sharding import mesh_rules_key
from repro.distributed.sharding import with_logical_constraint as wlc
from repro.models import layers as L
from repro.models.param import ParamSpec
from repro.ops.registry import override_key

Params = Dict[str, Any]


def _stack_specs(spec: Params, n: int) -> Params:
    """Prepend a 'layers' axis to every ParamSpec in a block spec tree."""
    return jax.tree.map(
        lambda s: ParamSpec((n,) + s.shape, ("layers",) + s.axes, s.dtype, s.init, s.scale),
        spec,
        is_leaf=lambda x: isinstance(x, ParamSpec),
    )


def _trace_context() -> Tuple:
    """What a trace of the model reads besides its arguments: the
    ``ops.use`` override stack, the scan-unroll probe flag and the ambient
    mesh rules.  The jitted prompt programs take it as a static argument,
    so a call under another context traces anew, as an eager call did."""
    return override_key(), unroll_scans_enabled(), mesh_rules_key()


class DecoderLM:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg.validate()

        # The prompt programs, jitted once per model (DESIGN.md §12): a
        # call whose shapes, static arguments and ``context`` were seen
        # before runs its compiled program, with no trace, lowering or
        # compile-cache load on the host.  ``context`` only keys the cache.
        # Nothing is donated: a staging cache may be extended twice.
        def prefill(params, tokens, patch_embeds, cache_t, moe_capacity, context):
            return self._prefill(params, tokens, patch_embeds, cache_t, moe_capacity)

        def prefill_extend(params, cache, tokens, moe_capacity, context):
            return self._prefill_extend(params, cache, tokens, moe_capacity)

        self.prompt_programs = (
            jax.jit(prefill, static_argnames=("cache_t", "moe_capacity", "context")),
            jax.jit(prefill_extend, static_argnames=("moe_capacity", "context")),
        )

    # -- parameters ---------------------------------------------------------

    def block_spec(self) -> Params:
        cfg = self.cfg
        spec: Params = {
            "ln1": L.spec_rmsnorm(cfg),
            "attn": L.spec_attention(cfg),
            "ln2": L.spec_rmsnorm(cfg),
        }
        if cfg.family == "moe":
            spec["moe"] = L.spec_moe(cfg)
        else:
            spec["mlp"] = L.spec_mlp(cfg)
        return spec

    def param_specs(self) -> Params:
        cfg = self.cfg
        specs: Params = {
            "embed": L.spec_embedding(cfg),
            "blocks": _stack_specs(self.block_spec(), cfg.num_layers),
            "final_norm": L.spec_rmsnorm(cfg),
            "unembed": L.spec_unembed(cfg),
        }
        if cfg.family == "vlm":
            specs["patch_proj"] = {
                "kernel": ParamSpec(
                    (cfg.frontend_dim or cfg.d_model, cfg.d_model),
                    ("embed", None), jnp.dtype(cfg.param_dtype), "fan_in",
                )
            }
        return specs

    # -- block --------------------------------------------------------------

    def _block(
        self,
        bp: Params,
        h: jax.Array,
        *,
        positions: Optional[jax.Array],
        cache: Optional[Params],
        kv_valid_len: Optional[jax.Array],
        paged_cache_t: Optional[int] = None,
        moe_capacity: Optional[int] = None,
    ) -> Tuple[jax.Array, Optional[Params], Tuple[jax.Array, jax.Array], Optional[jax.Array]]:
        cfg = self.cfg
        # named scopes tag the compiled ops (HLO op_name metadata), so a
        # device trace's ops map back to the block's parts
        with jax.named_scope("attention"):
            a, new_cache, kv = L.attention_block(
                bp["attn"], L.rmsnorm(bp["ln1"], h, cfg.norm_eps), cfg,
                causal=True, positions=positions,
                sliding_window=cfg.sliding_window, cache=cache,
                kv_valid_len=kv_valid_len, paged_cache_t=paged_cache_t,
            )
            h = h + L.attention_out(bp["attn"], a, cfg)
        moe_state = None
        with jax.named_scope("ffn"):
            hn = L.rmsnorm(bp["ln2"], h, cfg.norm_eps)
            if cfg.family == "moe":
                prior = cache.get("moe") if cache is not None else None
                if prior is not None or moe_capacity is not None:
                    # chunked prefill: global expert-queue positions + the
                    # full-sequence capacity keep dropping chunk-invariant
                    y, moe_state = L.moe(bp["moe"], hn, cfg, state=prior, capacity=moe_capacity)
                    h = h + y
                else:
                    h = h + L.moe(bp["moe"], hn, cfg)
            else:
                h = h + L.mlp(bp["mlp"], hn, cfg)
        return h, new_cache, kv, moe_state

    def _run_blocks(
        self,
        params: Params,
        h: jax.Array,
        *,
        positions: Optional[jax.Array] = None,
        caches: Optional[Params] = None,
        kv_valid_len: Optional[jax.Array] = None,
    ) -> Tuple[jax.Array, Optional[Params]]:
        cfg = self.cfg

        def body(carry, xs):
            bp = xs["p"]
            cache = xs.get("c")
            out, new_cache, _, _ = self._block(
                bp, carry, positions=positions, cache=cache,
                kv_valid_len=kv_valid_len,
            )
            if cfg.seq_parallel_activations:
                # shard the inter-block carry's seq dim over the model axis —
                # the remat-saved residual per layer shrinks by the TP degree
                out = wlc(out, ("batch", "act_seq", "embed"))
            return out, new_cache

        if cfg.remat:
            body = jax.checkpoint(body)

        if cfg.scan_layers:
            xs: Params = {"p": params["blocks"]}
            if caches is not None:
                xs["c"] = caches
            h, new_caches = L.scan_blocks(body, h, xs)
            return h, new_caches
        # unrolled (debug path)
        new_caches = []
        for i in range(cfg.num_layers):
            bp = jax.tree.map(lambda x: x[i], params["blocks"])
            xs = {"p": bp}
            if caches is not None:
                xs["c"] = jax.tree.map(lambda x: x[i], caches)
            h, nc = body(h, xs)
            new_caches.append(nc)
        if caches is not None:
            new_caches = jax.tree.map(lambda *xs: jnp.stack(xs), *new_caches)
        else:
            new_caches = None
        return h, new_caches

    # -- embedding helpers ----------------------------------------------------

    def _embed_inputs(
        self, params: Params, tokens: jax.Array, patch_embeds: Optional[jax.Array]
    ) -> Tuple[jax.Array, Optional[jax.Array], int]:
        """Returns (x, positions, n_prefix).  VLM prepends projected patches
        and builds M-RoPE (t, h, w) position ids; text uses 1-D positions."""
        cfg = self.cfg
        x = L.embed(params["embed"], tokens, cfg)
        b = tokens.shape[0]
        if cfg.family != "vlm" or patch_embeds is None:
            return x, None, 0
        dt = L.cdtype(cfg)
        patches = jnp.einsum(
            "bpd,dm->bpm", patch_embeds.astype(dt), params["patch_proj"]["kernel"].astype(dt)
        )
        n_patch = patches.shape[1]
        x = jnp.concatenate([patches, x], axis=1)
        # M-RoPE ids — patches: t=0, (h, w) on a stub grid; text: all equal,
        # offset past the patch grid extent.
        side = max(1, int(n_patch ** 0.5))
        hh = (jnp.arange(n_patch) // side).astype(jnp.int32)
        ww = (jnp.arange(n_patch) % side).astype(jnp.int32)
        ppos = jnp.stack([jnp.zeros_like(hh), hh, ww], axis=-1)  # [P, 3]
        t0 = side  # text starts after patch grid extent (qwen2-vl convention)
        tpos1 = t0 + jnp.arange(tokens.shape[1], dtype=jnp.int32)
        tpos = jnp.stack([tpos1, tpos1, tpos1], axis=-1)  # [T, 3]
        pos = jnp.concatenate([ppos, tpos], axis=0)[None]  # [1, P+T, 3]
        return x, jnp.broadcast_to(pos, (b,) + pos.shape[1:]), n_patch

    # -- public API -----------------------------------------------------------

    def forward(
        self,
        params: Params,
        tokens: jax.Array,
        *,
        patch_embeds: Optional[jax.Array] = None,
    ) -> jax.Array:
        """Full-sequence causal forward -> logits [B, T(+P), V]."""
        cfg = self.cfg
        x, positions, _ = self._embed_inputs(params, tokens, patch_embeds)
        h, _ = self._run_blocks(params, x, positions=positions)
        h = L.rmsnorm(params["final_norm"], h, cfg.norm_eps)
        return L.unembed(params["unembed"], h, cfg, params["embed"])

    def loss(self, params: Params, batch: Dict[str, jax.Array]) -> jax.Array:
        """Mean next-token CE.  batch: tokens [B,T], labels [B,T] (-1 = pad),
        optional patch_embeds."""
        logits = self.forward(
            params, batch["tokens"], patch_embeds=batch.get("patch_embeds")
        )
        labels = batch["labels"]
        if logits.shape[1] != labels.shape[1]:  # VLM prefix: no loss on patches
            logits = logits[:, logits.shape[1] - labels.shape[1]:]
        return cross_entropy(logits, labels)

    # -- serving --------------------------------------------------------------

    def cache_len(self, max_len: int) -> int:
        if self.cfg.sliding_window is not None:
            return min(max_len, self.cfg.sliding_window)
        return max_len

    def cache_spec(self, batch: int, max_len: int) -> Params:
        cfg = self.cfg
        t = self.cache_len(max_len)
        kv = (cfg.num_layers, batch, t, cfg.num_kv_heads, cfg.resolved_head_dim)
        axes = ("layers", "batch", "kv_seq", "kv_heads", None)
        dt = jnp.dtype(cfg.compute_dtype)
        return {
            "layers": {
                "k": ParamSpec(kv, axes, dt, "zeros"),
                "v": ParamSpec(kv, axes, dt, "zeros"),
            },
            "len": ParamSpec((), (), jnp.int32, "zeros"),
            # rope position of the next token — differs from "len" for VLM
            # (M-RoPE positions restart after the patch grid extent)
            "pos": ParamSpec((), (), jnp.int32, "zeros"),
        }

    # -- slot-pool serving (continuous batching) ------------------------------
    #
    # A slot pool is an ordinary decode cache whose "len"/"pos" entries are
    # [num_slots] vectors instead of scalars: each batch row ("slot") decodes
    # at its own depth.  ``decode_step`` handles both forms transparently
    # (see layers.attention_block's per-slot path); the helpers below manage
    # slot lifecycle for repro.serve.  DESIGN.md §6 documents the dataflow.

    def init_pool_cache(self, num_slots: int, max_len: int) -> Params:
        """Zeroed slot-pool cache: KV [L, S, T, Hkv, D], per-slot len/pos."""
        cfg = self.cfg
        t = self.cache_len(max_len)
        kv = (cfg.num_layers, num_slots, t, cfg.num_kv_heads, cfg.resolved_head_dim)
        dt = jnp.dtype(cfg.compute_dtype)
        return {
            "layers": {"k": jnp.zeros(kv, dt), "v": jnp.zeros(kv, dt)},
            "len": jnp.zeros((num_slots,), jnp.int32),
            "pos": jnp.zeros((num_slots,), jnp.int32),
        }

    def write_slot(self, pool: Params, cache: Params, slot: int) -> Params:
        """Insert a single-request prefill cache (batch 1) into pool ``slot``.

        The prefill must have used the pool's ``max_len`` so the cache seq
        dims line up; the freshly admitted request starts decoding at its
        own length on the next pool tick.
        """
        k1 = cache["layers"]["k"]
        pk = pool["layers"]["k"]
        if k1.shape[1] != 1:
            raise ValueError(f"write_slot expects a batch-1 prefill cache, got {k1.shape}")
        if k1.shape[2] != pk.shape[2]:
            raise ValueError(
                f"prefill cache length {k1.shape[2]} != pool length {pk.shape[2]}; "
                "prefill with the pool's max_len"
            )
        return {
            "layers": {
                "k": pk.at[:, slot].set(k1[:, 0].astype(pk.dtype)),
                "v": pool["layers"]["v"].at[:, slot].set(
                    cache["layers"]["v"][:, 0].astype(pk.dtype)
                ),
            },
            "len": pool["len"].at[slot].set(cache["len"].astype(jnp.int32)),
            "pos": pool["pos"].at[slot].set(cache["pos"].astype(jnp.int32)),
        }

    def reset_slot(self, pool: Params, slot: int) -> Params:
        """Retire ``slot``: zero its counters so its stale rows are masked.

        Note the counters regrow while the slot sits free — ``decode_step``
        advances the whole ``len`` vector every tick — so a free slot
        accumulates masked garbage that the next admission overwrites
        wholesale.  ``len == 0`` is NOT a free-slot predicate; the
        scheduler owns slot occupancy."""
        return {
            "layers": pool["layers"],
            "len": pool["len"].at[slot].set(0),
            "pos": pool["pos"].at[slot].set(0),
        }

    # -- paged slot pool (block-table KV cache) -------------------------------
    #
    # The paged pool replaces each slot's dense [T] KV row with a block
    # table over a flat [num_blocks, block_size] page pool (DESIGN.md §8;
    # host allocator: repro.serve.paged.BlockPool).  Logical row i of a
    # slot lives at (table[i // bs], i % bs), so gathering a table
    # reproduces the dense row bit-for-bit — paged greedy decode is
    # token-identical to the dense pool by construction.

    def init_paged_cache(
        self, num_blocks: int, block_size: int, num_slots: int,
        kv_dtype: str = "fp32",
    ) -> Params:
        """Zeroed page pool: KV [L, N, bs, Hkv, D], per-slot len/pos.

        ``kv_dtype != "fp32"`` stores quantized codes instead of values and
        adds ``k_scale``/``v_scale`` leaves — one float32 scale per
        (layer, block, kv_head) — initialized to ones so the scratch block
        and never-written pages decode to exact zeros (DESIGN.md §13).
        fp32 pools carry *no* scale leaves: ``"k_scale" in cache["layers"]``
        is the quantized-layout marker everywhere downstream.
        """
        cfg = self.cfg
        kvquant.validate_kv_dtype(kv_dtype)
        kv = (
            cfg.num_layers, num_blocks, block_size,
            cfg.num_kv_heads, cfg.resolved_head_dim,
        )
        dt = (
            jnp.dtype(cfg.compute_dtype)
            if kv_dtype == "fp32"
            else kvquant.storage_dtype(kv_dtype)
        )
        leaves = {"k": jnp.zeros(kv, dt), "v": jnp.zeros(kv, dt)}
        if kv_dtype != "fp32":
            sc = (cfg.num_layers, num_blocks, cfg.num_kv_heads)
            leaves["k_scale"] = jnp.ones(sc, jnp.float32)
            leaves["v_scale"] = jnp.ones(sc, jnp.float32)
        return {
            "layers": leaves,
            "len": jnp.zeros((num_slots,), jnp.int32),
            "pos": jnp.zeros((num_slots,), jnp.int32),
        }

    def write_slot_paged(
        self, pool: Params, cache: Params, slot: int, table: jax.Array
    ) -> Params:
        """Scatter a batch-1 prefill cache into the blocks of ``table``.

        The prefill rows are zero-padded up to the block grid, so a
        recycled block is overwritten *wholesale* — no stale rows from its
        previous owner survive inside the allocated table (rows past the
        grid are scratch and masked).  ``table`` is the [W] int32 block-id
        row the host allocator assigned to this request.
        """
        k1 = cache["layers"]["k"]
        pk = pool["layers"]["k"]
        if k1.shape[1] != 1:
            raise ValueError(f"write_slot_paged expects a batch-1 cache, got {k1.shape}")
        bs = pk.shape[2]
        w = table.shape[0]
        t1 = k1.shape[2]
        if t1 > w * bs:
            raise ValueError(
                f"prefill cache has {t1} rows but the table holds "
                f"{w} blocks x {bs} = {w * bs}"
            )
        pad = [(0, 0), (0, 0), (0, w * bs - t1), (0, 0), (0, 0)]

        def blocks(arr):  # [L, 1, T1, H, D] -> [L, W, bs, H, D]
            a = jnp.pad(arr, pad)[:, 0]
            lyr, _, h, d = a.shape
            return a.reshape(lyr, w, bs, h, d)

        kv_dtype = kvquant.dtype_of(pk.dtype)
        if kv_dtype != "fp32":
            # Prefill-time quantization: whole blocks at once, so each
            # block's scale is the true absmax over its rows — no clipping
            # on this path (DESIGN.md §13).
            kc, ks = kvquant.quantize_blocks(blocks(k1), kv_dtype)
            vc, vs = kvquant.quantize_blocks(blocks(cache["layers"]["v"]), kv_dtype)
            leaves = {
                "k": pk.at[:, table].set(kc),
                "v": pool["layers"]["v"].at[:, table].set(vc),
                "k_scale": pool["layers"]["k_scale"].at[:, table].set(ks),
                "v_scale": pool["layers"]["v_scale"].at[:, table].set(vs),
            }
        else:
            leaves = {
                "k": pk.at[:, table].set(blocks(k1).astype(pk.dtype)),
                "v": pool["layers"]["v"].at[:, table].set(
                    blocks(cache["layers"]["v"]).astype(pk.dtype)
                ),
            }
        return {
            "layers": leaves,
            "len": pool["len"].at[slot].set(cache["len"].astype(jnp.int32)),
            "pos": pool["pos"].at[slot].set(cache["pos"].astype(jnp.int32)),
        }

    def copy_block(self, pool: Params, src: jax.Array, dst: jax.Array) -> Params:
        """Copy one KV block (all layers) — the device half of the
        allocator's copy-on-fork hook (``BlockPool.ensure_writable``)."""
        pk, pv = pool["layers"]["k"], pool["layers"]["v"]
        leaves = {
            "k": pk.at[:, dst].set(pk[:, src]),
            "v": pv.at[:, dst].set(pv[:, src]),
        }
        for name in ("k_scale", "v_scale"):
            # quantized layout: the scale row shares its block's lifecycle,
            # so a CoW copy moves it too (DESIGN.md §13)
            if name in pool["layers"]:
                sp = pool["layers"][name]
                leaves[name] = sp.at[:, dst].set(sp[:, src])
        return {
            "layers": leaves,
            "len": pool["len"],
            "pos": pool["pos"],
        }

    def decode_step_paged(
        self,
        params: Params,
        cache: Params,
        tokens: jax.Array,
        block_tables: jax.Array,  # [S, W] int32 (host allocator state)
        *,
        cache_t: int,
    ) -> Tuple[jax.Array, Params]:
        """One paged token step.  tokens [S, 1] -> (logits [S, 1, V], cache').

        ``block_tables`` is per-tick host input (the allocator appends
        blocks between ticks); ``cache_t`` is the static logical per-slot
        row count (= ``cache_len(max_len)``) — it sizes the gathered view
        and the sliding-window ring modulo.
        """
        cfg = self.cfg
        x = L.embed(params["embed"], tokens, cfg)
        pos0 = cache.get("pos", cache["len"])
        pos = pos0.astype(jnp.int32)[:, None]  # [S, 1]
        if cfg.mrope_sections:
            pos = jnp.stack([pos, pos, pos], axis=-1)

        kv_leaves = tuple(cache["layers"])  # += k/v_scale when quantized

        # The page pools ride the scan *carry*, whole: each layer writes
        # its one new row per slot in place and its attention reads its
        # own slab through the layer index.  Scanned as xs/ys instead,
        # the pools would be rebuilt as fresh stacked outputs — a second
        # full pool in HBM every tick.
        def body(carry, xs):
            h, pools = carry
            out, new_c, _, _ = self._block(
                xs["p"], h, positions=pos,
                cache={**pools, "len": cache["len"], "tables": block_tables,
                       "layer": xs["layer"]},
                kv_valid_len=None, paged_cache_t=cache_t,
            )
            return (out, {name: new_c[name] for name in kv_leaves}), None

        (h, new_layer_caches), _ = L.scan_blocks(
            body, (x, cache["layers"]),
            {"p": params["blocks"],
             "layer": jnp.arange(cfg.num_layers, dtype=jnp.int32)},
        )
        with jax.named_scope("unembed"):
            h = L.rmsnorm(params["final_norm"], h, cfg.norm_eps)
            logits = L.unembed(params["unembed"], h, cfg, params["embed"])
        new_cache = {
            "layers": {name: new_layer_caches[name] for name in kv_leaves},
            "len": cache["len"] + 1,
            "pos": cache.get("pos", cache["len"]) + 1,
        }
        return logits, new_cache

    def prefill(
        self,
        params: Params,
        tokens: jax.Array,
        max_len: int,
        *,
        patch_embeds: Optional[jax.Array] = None,
        cache_t: Optional[int] = None,
        moe_capacity: Optional[int] = None,
    ) -> Tuple[jax.Array, Params]:
        """Process a prompt, return (last-position logits, primed cache).

        ``cache_t`` overrides the cache capacity (default
        ``cache_len(max_len)``) — chunked prefill stages into a *linear*
        buffer sized past the sliding window so later chunks can append
        (``prefill_extend``) before ``finalize_ring_cache`` folds it.
        ``moe_capacity`` threads the full-sequence expert capacity through
        (and adds per-layer ``moe`` queue counts to the returned cache) so
        a chunked MoE prefill drops exactly the tokens a monolithic one
        would.  Runs through ``prompt_programs[0]``: one compile per
        distinct shape, capacity and trace context.
        """
        ct = cache_t if cache_t is not None else self.cache_len(max_len)
        return self.prompt_programs[0](
            params, tokens, patch_embeds, cache_t=ct,
            moe_capacity=moe_capacity, context=_trace_context(),
        )

    def _prefill(self, params, tokens, patch_embeds, ct, moe_capacity):
        cfg = self.cfg
        x, positions, _ = self._embed_inputs(params, tokens, patch_embeds)

        def body(carry, bp):
            out, _, (k, v), ms = self._block(
                bp, carry, positions=positions, cache=None, kv_valid_len=None,
                moe_capacity=moe_capacity,
            )
            ys = {"k": k, "v": v}
            if ms is not None:
                ys["moe"] = ms
            return out, ys

        if cfg.remat:
            body = jax.checkpoint(body)
        h, kvs = L.scan_blocks(body, x, params["blocks"])
        h = L.rmsnorm(params["final_norm"], h, cfg.norm_eps)
        logits = L.unembed(params["unembed"], h[:, -1:], cfg, params["embed"])

        seq = x.shape[1]
        if cfg.sliding_window is None and seq > ct:
            raise ValueError(
                f"prefill length {seq} (incl. any patch prefix) exceeds cache "
                f"capacity {ct}; pass a larger max_len"
            )
        k_init, v_init = L.fit_window_cache(kvs["k"], kvs["v"], 2, ct, seq)
        if positions is not None:  # VLM: next M-RoPE temporal position
            next_pos = positions[0, -1, 0].astype(jnp.int32) + 1
        else:
            next_pos = jnp.asarray(seq, jnp.int32)
        layer_caches = {"k": k_init, "v": v_init}
        if "moe" in kvs:
            layer_caches["moe"] = kvs["moe"]
        cache = {
            "layers": layer_caches,
            "len": jnp.asarray(seq, jnp.int32),
            "pos": next_pos,
        }
        return logits, cache

    def prefill_extend(
        self,
        params: Params,
        cache: Params,
        tokens: jax.Array,
        *,
        moe_capacity: Optional[int] = None,
    ) -> Tuple[jax.Array, Params]:
        """Append a prompt chunk to a *linear* staging cache.

        tokens [1, c] land at rows ``[len, len+c)`` of the staging buffer
        (the attention append path: queries at offset ``len``, causal +
        sliding-window masking against every cached row), so running a
        prompt through ``prefill`` + ``prefill_extend`` chunks produces the
        same KV rows and final logits as one monolithic ``prefill`` — the
        bit-identity contract chunked serving relies on (DESIGN.md §12).
        Requires the staging buffer to be strictly longer than the sliding
        window (the ring in-place path only supports single-token writes).
        Runs through ``prompt_programs[1]``, which donates nothing.
        """
        return self.prompt_programs[1](
            params, cache, tokens, moe_capacity=moe_capacity,
            context=_trace_context(),
        )

    def _prefill_extend(self, params, cache, tokens, moe_capacity):
        cfg = self.cfg
        b, c = tokens.shape
        x = L.embed(params["embed"], tokens, cfg)
        pos0 = cache.get("pos", cache["len"]).astype(jnp.int32)
        pos = pos0 + jnp.arange(c, dtype=jnp.int32)[None]
        pos = jnp.broadcast_to(pos, (b, c))
        if cfg.mrope_sections:
            pos = jnp.stack([pos, pos, pos], axis=-1)

        def body(carry, xs):
            out, new_c, _, ms = self._block(
                xs["p"], carry, positions=pos,
                cache={**xs["c"], "len": cache["len"]},
                kv_valid_len=None, moe_capacity=moe_capacity,
            )
            ys = {"k": new_c["k"], "v": new_c["v"]}
            if ms is not None:
                ys["moe"] = ms
            return out, ys

        h, new_layers = L.scan_blocks(
            body, x, {"p": params["blocks"], "c": cache["layers"]}
        )
        # rmsnorm is positionwise, so norming the last row alone matches
        # the monolithic norm-then-slice bit for bit
        h = L.rmsnorm(params["final_norm"], h[:, -1:], cfg.norm_eps)
        logits = L.unembed(params["unembed"], h, cfg, params["embed"])
        new_cache = {
            "layers": new_layers,
            "len": cache["len"] + c,
            "pos": cache.get("pos", cache["len"]) + c,
        }
        return logits, new_cache

    def gather_prefix_cache(
        self, pool: Params, blocks, rows: int, capacity: int
    ) -> Params:
        """Batch-1 linear staging cache seeded from cached prefix ``blocks``.

        The prefix-cache admission path: the trie matched ``rows`` prompt
        rows living in ``blocks`` (all full, ``rows == len(blocks) *
        block_size``), and the uncached suffix continues from there via
        ``prefill_extend``.  Rows past ``rows`` are zero until written —
        masked garbage, exactly like a monolithic prefill's padding.
        """
        pk, pv = pool["layers"]["k"], pool["layers"]["v"]
        bs = pk.shape[2]
        if rows != len(blocks) * bs:
            raise ValueError(f"prefix rows {rows} != {len(blocks)} blocks x {bs}")
        tab = jnp.asarray(list(blocks), jnp.int32)
        quantized = "k_scale" in pool["layers"]
        dt = jnp.dtype(self.cfg.compute_dtype)

        def gather(a, scale):  # [L, N, bs, H, D] -> [L, 1, capacity, H, D]
            g = a[:, tab]
            if scale is not None:
                # dense staging holds *values*: restore the cached prefix
                # blocks through their own scale rows (same codes * scale
                # expression the decode kernel evaluates — DESIGN.md §13)
                g = kvquant.decode(g, scale[:, tab][:, :, None, :, None]).astype(dt)
            lyr, w, _, hh, dd = g.shape
            g = g.reshape(lyr, 1, w * bs, hh, dd)
            return jnp.pad(g, [(0, 0), (0, 0), (0, capacity - w * bs), (0, 0), (0, 0)])

        rows32 = jnp.asarray(rows, jnp.int32)
        return {
            "layers": {
                "k": gather(pk, pool["layers"]["k_scale"] if quantized else None),
                "v": gather(pv, pool["layers"]["v_scale"] if quantized else None),
            },
            "len": rows32,
            "pos": rows32,
        }

    def finalize_ring_cache(self, cache: Params, wlen: int) -> Params:
        """Fold a linear staging cache into the ring layout (slot = pos % wlen).

        The traced-length counterpart of ``layers.fit_window_cache``: ring
        slot ``s`` receives the *latest* staged token congruent to ``s``
        (``j = s + floor((T-1-s)/wlen) * wlen``), with a traced ``T`` so
        chunk-count differences don't retrace.  Slots ``s >= T`` clip to
        row 0 — masked garbage, decode only trusts ``min(len, wlen)`` rows.
        """
        k = cache["layers"]["k"]
        T = cache["len"].astype(jnp.int32)
        s = jnp.arange(wlen, dtype=jnp.int32)
        j = jnp.clip(s + ((T - 1 - s) // wlen) * wlen, 0, k.shape[2] - 1)

        def take(a):
            return jnp.take(a, j, axis=2)

        return {
            "layers": {"k": take(k), "v": take(cache["layers"]["v"])},
            "len": cache["len"],
            "pos": cache["pos"],
        }

    def moe_prefill_capacity(self, rows: int) -> Optional[int]:
        """Full-sequence expert capacity for a ``rows``-row prompt (None
        for non-MoE archs) — what every chunk of that prompt must use."""
        if self.cfg.family != "moe":
            return None
        return L.moe_capacity(self.cfg, rows)

    def decode_step(
        self, params: Params, cache: Params, tokens: jax.Array
    ) -> Tuple[jax.Array, Params]:
        """One token step.  tokens [B, 1] -> (logits [B, 1, V], new cache)."""
        cfg = self.cfg
        x = L.embed(params["embed"], tokens, cfg)
        b = tokens.shape[0]
        # decode rope positions: the positional counter (== len except VLM)
        pos0 = cache.get("pos", cache["len"])
        if jnp.ndim(pos0) == 1:  # per-slot pool cache: [B] counters
            pos = pos0.astype(jnp.int32)[:, None]
        else:
            pos = (pos0 + jnp.arange(1, dtype=jnp.int32))[None]
            pos = jnp.broadcast_to(pos, (b, 1))
        if cfg.mrope_sections:
            pos = jnp.stack([pos, pos, pos], axis=-1)

        def body(carry, xs):
            out, new_c, _, _ = self._block(
                xs["p"], carry, positions=pos, cache={**xs["c"], "len": cache["len"]},
                kv_valid_len=None,
            )
            return out, {"k": new_c["k"], "v": new_c["v"]}

        h, new_layer_caches = L.scan_blocks(body, x, {"p": params["blocks"], "c": cache["layers"]})
        with jax.named_scope("unembed"):
            h = L.rmsnorm(params["final_norm"], h, cfg.norm_eps)
            logits = L.unembed(params["unembed"], h, cfg, params["embed"])
        new_cache = {
            "layers": {"k": new_layer_caches["k"], "v": new_layer_caches["v"]},
            "len": cache["len"] + 1,
            "pos": cache.get("pos", cache["len"]) + 1,
        }
        return logits, new_cache


def cross_entropy(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Mean CE over positions with label >= 0 (f32 reductions)."""
    lg = logits.astype(jnp.float32)
    m = jnp.max(lg, axis=-1, keepdims=True)
    lse = m[..., 0] + jnp.log(jnp.sum(jnp.exp(lg - m), axis=-1))
    safe = jnp.maximum(labels, 0)
    picked = jnp.take_along_axis(lg, safe[..., None], axis=-1)[..., 0]
    nll = lse - picked
    mask = (labels >= 0).astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
