"""Serving engines: lockstep batch generation and continuous batching.

Two engines share the model, the KV-cache machinery, and STAR-softmax
sampling (temperature folded into the logits before quantization — the
paper's precision argument applies to the output distribution too):

* :class:`ServeEngine` — the lockstep baseline: one fixed batch prefills
  together, decodes together, finishes together.  Simple, and the right
  tool when every request has the same shape; pathological under
  heterogeneous traffic, where the whole batch waits for its longest
  member.

* :class:`ContinuousBatchingEngine` — a slot-pool engine (the tentpole).
  Requests are admitted into a fixed pool of KV-cache slots as they arrive
  (``SlotScheduler`` handles the lifecycle: FIFO admission, backpressure
  when the pool is full, immediate slot reuse on completion).  Every tick
  runs **one** jitted ``decode_step`` across the whole pool; per-slot
  ``len``/``pos`` vectors in the cache (see ``DecoderLM.init_pool_cache``
  and the per-slot path in ``layers.attention_block``) let each slot attend
  at its own depth, so a newly admitted 8-token prompt and a 400-token
  veteran decode side by side in the same MXU pass.  This is the paper's
  fine-grained pipeline argument lifted to the request level: throughput
  comes from never letting a lane idle.

Slot lifecycle (one ``step()`` tick)::

    admit:   pending ──> free slot: prefill(batch=1) -> write_slot(pool)
                          sample token 0 from the prefill logits
    decode:  one jitted decode_step over all S slots  [S,1] -> [S,1,V]
             sample token t per active slot
    retire:  finished slots (budget / EOS) release immediately;
             reset_slot zeroes the slot's counters (stale rows masked;
             free-slot counters regrow with the pool-wide tick — the
             scheduler, not len, is the source of truth for occupancy)

The engine offers two KV layouts (``ContinuousConfig.kv_layout``): the
dense per-slot pool above, and the **paged** block-pool cache (DESIGN.md
§8, ``serve/paged.py``) where admission allocates fixed-size token blocks,
decode appends blocks as slots cross block boundaries, and pool exhaustion
*preempts* the latest-admitted slot — its blocks return to the free list
and its request requeues at the front with generated tokens preserved.
``ops.use(attention="paged")`` (or an ``attn_impl="paged"`` config) flips
the layout without touching engine construction.

Greedy continuous-batching output is bit-identical to sequential
``ServeEngine.generate`` calls for the same prompts (tests/test_serve.py);
with temperature, each request gets its own PRNG stream (folded from its
uid), so sampled output is also independent of pool co-tenancy.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import ops
from repro.obs import MetricsRegistry, NullTracer, Tracer, get_tracer
from repro.configs.base import ModelConfig
from repro.models.registry import build_model
from repro.models.transformer import DecoderLM
from repro.ops.registry import active_overrides
from repro.serve.paged import SCRATCH_BLOCK, BlockPool, PrefixCache, bucket_blocks
from repro.serve.scheduler import Request, Slot, SlotScheduler

PyTree = Any


@dataclasses.dataclass
class ServeConfig:
    max_len: int = 512
    temperature: float = 0.0  # 0 = greedy
    star_sampling: bool = True  # STAR softmax on the output distribution


def sample_token(
    logits: jax.Array,  # [..., V]
    key: jax.Array,
    cfg: ModelConfig,
    serve_cfg: ServeConfig,
    guard: Optional["ops.AccuracyGuard"] = None,
) -> jax.Array:
    """Greedy or temperature sampling, through the STAR engine when
    configured (the quantized LUT softmax shapes the sampling distribution
    exactly like it shapes attention rows).

    ``guard`` routes the sampling softmax through the accuracy guard
    (eager call sites only — it compares against the exact oracle on the
    host, see ``repro.ops.guard``)."""
    t = serve_cfg.temperature
    if t <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    scaled = logits.astype(jnp.float32) / t
    spec = cfg.softmax_spec
    if serve_cfg.star_sampling and spec.kind != "exact":
        probs = ops.softmax(scaled, spec, guard=guard)
        return jax.random.categorical(
            key, jnp.log(jnp.maximum(probs, 1e-20)), axis=-1
        ).astype(jnp.int32)
    return jax.random.categorical(key, scaled, axis=-1).astype(jnp.int32)


class ServeEngine:
    """Lockstep batch engine: one prefill, then synchronized decode."""

    def __init__(self, model_cfg: ModelConfig, params: PyTree, serve_cfg: ServeConfig = ServeConfig()):
        self.cfg = model_cfg
        self.params = params
        self.serve_cfg = serve_cfg
        self.model = build_model(model_cfg)
        self._decode = jax.jit(self.model.decode_step)

    def _sample(self, logits: jax.Array, key: jax.Array) -> jax.Array:
        return sample_token(logits, key, self.cfg, self.serve_cfg)

    def generate(
        self,
        prompts: jax.Array,  # [B, T] token prompts
        num_tokens: int,
        *,
        key: Optional[jax.Array] = None,
        **frontend,  # patch_embeds / src_embeds stubs
    ) -> Tuple[jax.Array, Dict[str, Any]]:
        key = key if key is not None else jax.random.PRNGKey(0)
        b, t = prompts.shape
        max_len = self.serve_cfg.max_len
        logits, cache = self.model.prefill(self.params, prompts, max_len, **frontend)
        outs = []
        tok = self._sample(logits[:, -1], key)[:, None]
        outs.append(tok)
        for i in range(num_tokens - 1):
            key, sub = jax.random.split(key)
            logits, cache = self._decode(self.params, cache, tok)
            tok = self._sample(logits[:, -1], sub)[:, None]
            outs.append(tok)
        generated = jnp.concatenate(outs, axis=1)
        return generated, {"cache_len": int(jax.device_get(cache["len"]))}


# ---------------------------------------------------------------------------
# Continuous batching


@dataclasses.dataclass
class ContinuousConfig:
    num_slots: int = 8  # KV-cache pool size (max concurrent requests)
    max_len: int = 512  # per-slot cache capacity (prompt + generation)
    temperature: float = 0.0
    star_sampling: bool = True
    # Paged KV cache (DESIGN.md §8).  "dense" keeps the PR-1 per-slot
    # buffers; "paged" stores K/V in fixed-size token blocks behind
    # per-request block tables (serve/paged.py) so memory tracks live
    # tokens.  ``ops.use(attention="paged")`` — or a config whose
    # attention impl is "paged" — flips the layout too.
    kv_layout: str = "dense"  # dense | paged
    kv_block_size: int = 16  # tokens per KV block
    # usable blocks in the pool (scratch excluded); None sizes it to the
    # dense-equivalent capacity num_slots * ceil(cache_len / block_size)
    kv_pool_blocks: Optional[int] = None
    # Shared-prefix KV cache (DESIGN.md §12): a radix trie over token-id
    # block chunks maps a new request's longest cached prefix to existing
    # pool blocks (refcount++), so admission skips prefill for the shared
    # prefix.  Paged layout only; rings and MoE archs silently opt out
    # (their KV/expert state is not prefix-local — see PrefixCache docs).
    prefix_cache: bool = False
    # Chunked prefill: budget of prompt tokens processed per engine tick.
    # Admitted prompts stream through in power-of-two chunks interleaved
    # with decode ticks instead of head-of-line-blocking the pool; None
    # keeps the monolithic admission prefill.
    prefill_chunk_tokens: Optional[int] = None
    # Quantized KV page storage (DESIGN.md §13): "int8" / "fp8_e4m3" store
    # codes + per-(block, head) scale pages in the page pool and the decode
    # kernel dequantizes in-kernel.  Paged layout only — the dense per-slot
    # pool has no block granularity to hang scales off.
    kv_dtype: str = "fp32"  # fp32 | int8 | fp8_e4m3
    # Accuracy guard on the sampling softmax (DESIGN.md §9): sampled
    # comparison against the exact oracle, fallback to a clean backend
    # when a degraded (faulty / over-quantized) spec exceeds tolerance.
    # Counters surface through ``ContinuousBatchingEngine.stats()``.
    guard: Optional["ops.GuardConfig"] = None

    def as_serve_config(self) -> ServeConfig:
        return ServeConfig(self.max_len, self.temperature, self.star_sampling)


@dataclasses.dataclass
class TokenEvent:
    """One emitted token: streamed to ``on_token`` and returned by step()."""

    uid: int
    token: int
    index: int  # 0-based position within the request's generation
    finished: bool
    slot: int  # the slot that produced it (its row in ``last_logits``)


class ContinuousBatchingEngine:
    """Slot-pool serving: admit, decode the whole pool per tick, retire.

    Host-side control (the :class:`SlotScheduler`) decides *which* requests
    occupy which slots; the device-side tick is a single jitted
    ``decode_step`` over the ``[num_slots, 1]`` token matrix.  Free slots
    decode garbage that is masked (their ``len`` counter is 0) and simply
    discarded — the fixed shape is what keeps the step jit-stable.
    """

    def __init__(
        self,
        model_cfg: ModelConfig,
        params: PyTree,
        cb_cfg: ContinuousConfig = ContinuousConfig(),
        *,
        base_key: Optional[jax.Array] = None,
        on_token: Optional[Callable[[TokenEvent], None]] = None,
        tracer: Optional[Tracer | NullTracer] = None,
        metrics: Optional[MetricsRegistry] = None,
        clock: Optional[Callable[[], float]] = None,
    ):
        self.cfg = model_cfg
        self.params = params
        self.cb = cb_cfg
        # Observability (DESIGN.md §10).  The tracer binds at construction:
        # the global no-op singleton unless obs.enable_tracing() ran first
        # (or one is injected).  Metrics live in a per-engine registry so
        # stats() snapshots are isolated; ``clock`` is injectable for
        # deterministic latency tests (tests/test_obs_serve.py).
        self.tracer = tracer if tracer is not None else get_tracer()
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self._clock = clock if clock is not None else time.perf_counter
        reg = self.metrics
        self._m_submitted = reg.counter("serve.requests.submitted")
        self._m_admitted = reg.counter(
            "serve.requests.admitted", "admissions incl. re-admissions")
        self._m_finished = reg.counter("serve.requests.finished")
        self._m_preempted = reg.counter("serve.requests.preempted")
        self._m_tokens = reg.counter("serve.tokens.generated")
        self._h_ttft = reg.histogram(
            "serve.ttft_s", "submit -> first token (end-to-end, survives "
            "preemption)")
        self._h_itl = reg.histogram(
            "serve.itl_s", "inter-token latency per request")
        self._h_queue = reg.histogram(
            "serve.queue_wait_s", "pending-queue wait per admission stint")
        self._g_queue = reg.gauge("serve.queue.depth")
        self._g_active = reg.gauge("serve.slots.active")
        # Transfer / retrace accounting (DESIGN.md §11): counted bytes the
        # tick moves across the host-device boundary, the counted KV bytes
        # decode reads out of the page pool (traffic model, not a
        # measurement), and the pooled jit-cache entry count.
        self._m_h2d = reg.counter(
            "serve.bytes.h2d", "host->device bytes per tick (token inputs, "
            "dirty table rows, sampling uid/step vectors)")
        self._m_d2h = reg.counter(
            "serve.bytes.d2h", "device->host bytes per tick (the sampled "
            "token vector; admission adds one token per prefill)")
        self._m_gather = reg.counter(
            "kv.gather.bytes", "counted K+V bytes decode reads from the KV "
            "pool (ops.paged_gather_bytes traffic model)")
        self._g_jit = reg.gauge(
            "serve.jit.entries", "pooled jit-cache entries across the "
            "engine's compiled callables")
        self.model = build_model(model_cfg)
        if not isinstance(self.model, DecoderLM):
            raise ValueError(
                "continuous batching needs the per-slot KV-cache pool, which "
                f"only attention-family models implement (got {model_cfg.family!r})"
            )
        self.scheduler = SlotScheduler(cb_cfg.num_slots)
        # KV layout: the config picks it, and the "paged" marker impl —
        # via ops.use(attention="paged") or the config's own attention
        # spec — flips the whole serve stack to the block-pool cache.
        layout = cb_cfg.kv_layout
        if (
            active_overrides("attention").get("impl") == "paged"
            or model_cfg.attention_spec.impl == "paged"
        ):
            layout = "paged"
        if layout not in ("dense", "paged"):
            raise ValueError(f"kv_layout must be 'dense' or 'paged', got {layout!r}")
        self.kv_layout = layout
        self._cache_t = self.model.cache_len(cb_cfg.max_len)
        # ring caches (sliding window shorter than max_len) wrap in place:
        # their blocks are allocated once per admission, never appended
        self._ring = (
            model_cfg.sliding_window is not None
            and self._cache_t <= model_cfg.sliding_window
        )
        if layout == "paged":
            bs = cb_cfg.kv_block_size
            self._slot_blocks = -(-self._cache_t // bs)  # table width W
            usable = cb_cfg.kv_pool_blocks
            if usable is None:
                usable = cb_cfg.num_slots * self._slot_blocks
            self.block_pool = BlockPool(
                usable + 1, bs,  # +1: scratch block 0
                kv_dtype=cb_cfg.kv_dtype, metrics=self.metrics,
            )
            if self._ring and self._slot_blocks > self.block_pool.usable_blocks:
                raise ValueError(
                    f"a sliding-window ring needs {self._slot_blocks} blocks "
                    f"per slot but the pool only has "
                    f"{self.block_pool.usable_blocks}; raise kv_pool_blocks"
                )
            self.pool = self.model.init_paged_cache(
                usable + 1, bs, cb_cfg.num_slots, kv_dtype=cb_cfg.kv_dtype
            )
            self._tables = np.full(
                (cb_cfg.num_slots, self._slot_blocks), SCRATCH_BLOCK, np.int32
            )
            self._rows = np.zeros(cb_cfg.num_slots, np.int64)  # KV rows written
            # Device-resident mirror of the block tables (DESIGN.md §11):
            # the tick reads this array directly instead of uploading the
            # whole [S, W] host table every step.  Host-side allocator
            # edits mark their slot dirty; the flush before decode pushes
            # only the dirty rows through a donated row update, so steady
            # decode (no allocation churn) uploads zero table bytes.
            self._tables_dev = jnp.full(
                (cb_cfg.num_slots, self._slot_blocks), SCRATCH_BLOCK, jnp.int32
            )
            self._dirty_tables: set = set()
            self._push_row = jax.jit(
                lambda tab, i, row: tab.at[i].set(row), donate_argnums=(0,)
            )
            # slot index stays a *traced* argument (``.at[slot].set`` takes
            # a dynamic index) so the admission write compiles per bucketed
            # table width only — not per (slot, width) pair
            self._write_slot_paged = jax.jit(
                self.model.write_slot_paged, donate_argnums=(0,)
            )
            self.preemptions = 0  # OOM evictions (requeued, not dropped)
            self.peak_used_blocks = 0
        else:
            if cb_cfg.kv_dtype != "fp32":
                raise ValueError(
                    f"kv_dtype={cb_cfg.kv_dtype!r} requires kv_layout='paged' "
                    "(scales are per-block; the dense per-slot pool has no "
                    "blocks) — pass kv_layout='paged' or drop kv_dtype"
                )
            self.block_pool = None
            self.pool = self.model.init_pool_cache(cb_cfg.num_slots, cb_cfg.max_len)
            # donate the pool everywhere it is threaded through: the tick,
            # the admission write, and the retirement reset all update it in
            # place instead of copying the whole [L, S, T, H, D] pool
            # (self.pool is rebound to the result each call, so the old
            # buffer is never live)
            self._write_slot = jax.jit(
                self.model.write_slot, donate_argnums=(0,))
        self._reset_slot = jax.jit(
            self.model.reset_slot, donate_argnums=(0,))
        # Shared-prefix cache + chunked prefill (DESIGN.md §12).  Either
        # flag routes admission through the staging path; with both off the
        # monolithic admission prefill below is untouched.
        if cb_cfg.prefill_chunk_tokens is not None and cb_cfg.prefill_chunk_tokens < 1:
            raise ValueError(
                f"prefill_chunk_tokens must be >= 1, got {cb_cfg.prefill_chunk_tokens}"
            )
        if cb_cfg.prefix_cache and layout != "paged":
            raise ValueError(
                "prefix_cache requires kv_layout='paged' (the dense pool has "
                "no shareable blocks); pass kv_layout='paged' or drop the flag"
            )
        self._chunked = cb_cfg.prefill_chunk_tokens is not None or cb_cfg.prefix_cache
        self.prefix: Optional[PrefixCache] = None
        if (
            cb_cfg.prefix_cache
            and not self._ring
            and model_cfg.family != "moe"
        ):
            # rings opt out (a wrapped window no longer holds the prefix
            # rows a later request would adopt) and so do MoE archs (expert
            # queue positions are sequence-global, so cached prefix KV is
            # not sufficient state to resume from) — both still get chunked
            # prefill, just no cross-request sharing
            self.prefix = PrefixCache(self.block_pool, metrics=self.metrics)
        self._staging: Dict[int, Dict[str, Any]] = {}
        self._serve_cfg = cb_cfg.as_serve_config()
        # one stateful guard for the engine's lifetime: counters accumulate
        # across ticks and the trip latch persists (degraded part stays on
        # the clean path once caught)
        self.guard = (
            ops.AccuracyGuard(cb_cfg.guard) if cb_cfg.guard is not None else None
        )
        self._base_key = base_key if base_key is not None else jax.random.PRNGKey(0)
        self._on_token = on_token
        self._inputs = np.zeros((cb_cfg.num_slots, 1), np.int32)  # next token per slot
        self._frontend: Dict[int, Dict[str, jax.Array]] = {}
        self.ticks = 0  # decode ticks executed (for utilization accounting)
        # the last decode tick's [S, V] logits, left on device: a decode
        # event's logits are ``last_logits[ev.slot]`` (parity checks)
        self.last_logits: Optional[jax.Array] = None
        self._tick = self._build_tick()

    def _build_tick(self):
        """The fused device tick: decode the whole pool AND sample every
        slot inside one jitted program, so a steady tick performs a single
        D2H transfer — the ``[S]`` sampled-token vector (DESIGN.md §11).

        Free slots sample garbage from garbage keys; the host discards
        them (the scheduler owns occupancy).  The guarded sampling path
        cannot fold in — the accuracy guard compares against the exact
        oracle on the host — so the tick also returns the last-token
        logits as a *device* array: the guard path fetches it, everyone
        else never does.
        """
        cfg, serve_cfg = self.cfg, self._serve_cfg
        model, cache_t = self.model, self._cache_t
        base_key, paged = self._base_key, self.kv_layout == "paged"

        def tick(params, pool, inputs, tables, uids, steps):
            if paged:
                logits, pool = model.decode_step_paged(
                    params, pool, inputs, tables, cache_t=cache_t
                )
            else:
                logits, pool = model.decode_step(params, pool, inputs)
            last = logits[:, -1]  # [S, V]
            if serve_cfg.temperature <= 0.0:
                sampled = jnp.argmax(last, axis=-1).astype(jnp.int32)
            else:
                keys = jax.vmap(
                    lambda u, i: jax.random.fold_in(
                        jax.random.fold_in(base_key, u), i
                    )
                )(uids, steps)
                sampled = jax.vmap(
                    lambda lg, k: sample_token(lg, k, cfg, serve_cfg)
                )(last, keys)
            return sampled, last, pool

        return jax.jit(tick, donate_argnums=(1,))

    def jit_cache_entries(self) -> int:
        """Pooled compiled-variant count across the engine's jitted
        callables — the retrace observable (tests/test_serve_retrace.py):
        a repeated workload must not grow it, and mixed-length paged
        traffic must grow the admission write O(log W), not O(n)."""
        fns = [self._tick, self._reset_slot]
        fns.append(
            self._write_slot_paged if self.kv_layout == "paged"
            else self._write_slot
        )
        if self.kv_layout == "paged":
            fns.append(self._push_row)
        return int(sum(f._cache_size() for f in fns))

    # -- submission ---------------------------------------------------------

    def submit(
        self,
        prompt: Sequence[int] | np.ndarray,
        max_new_tokens: int,
        *,
        eos_id: Optional[int] = None,
        arrival_time: float = 0.0,
        **frontend,
    ) -> int:
        """Queue a request (never blocks); returns its uid."""
        prefix = self._prefix_rows(frontend)
        need = prefix + len(prompt) + max_new_tokens - 1
        if self.cfg.sliding_window is None:
            # decode writes prompt + (max_new_tokens - 1) K/V rows (the last
            # sampled token is never fed back); past capacity the per-slot
            # write would silently drop rows, so reject up front
            if need > self.cb.max_len:
                raise ValueError(
                    f"request needs {need} cache rows (prompt {len(prompt)} "
                    f"+ prefix {prefix} + {max_new_tokens} new tokens) but "
                    f"the pool was built with max_len={self.cb.max_len}"
                )
        if self.kv_layout == "paged":
            # a request larger than the whole pool could never be admitted,
            # even with every other slot preempted — reject it up front
            blocks = (
                self._slot_blocks if self._ring
                else self.block_pool.blocks_for_tokens(need)
            )
            if blocks > self.block_pool.usable_blocks:
                raise ValueError(
                    f"request needs {blocks} KV blocks "
                    f"({need} rows at block_size="
                    f"{self.block_pool.block_size}) but the pool only has "
                    f"{self.block_pool.usable_blocks}; raise kv_pool_blocks "
                    f"or kv_block_size, or split the request"
                )
        uid = self.scheduler.submit(
            prompt, max_new_tokens, eos_id=eos_id, arrival_time=arrival_time
        )
        if frontend:
            self._frontend[uid] = {k: jnp.asarray(v) for k, v in frontend.items()}
        now = self._clock()
        req = self.scheduler.pending[-1]
        req.submit_time = req.enqueued_at = now
        self._m_submitted.inc()
        self._g_queue.set(len(self.scheduler.pending))
        tracer = self.tracer
        if tracer.enabled:
            tracer.instant("serve.submit", uid=uid, prompt_len=len(prompt),
                           max_new_tokens=max_new_tokens)
            # one async track per request, open from submit to finish —
            # Perfetto renders queue wait + every decode stint on one row
            tracer.async_begin("request", uid)
        return uid

    # -- the tick -----------------------------------------------------------

    def _prefix_rows(self, frontend: Dict[str, Any]) -> int:
        """KV rows the frontend prepends before the prompt (VLM patches).
        Used by both the submit-time capacity check and the admission
        block allocation — one definition so they can never diverge."""
        if self.cfg.family == "vlm" and "patch_embeds" in frontend:
            return self.cfg.num_patches
        return 0

    def _request_key(self, req: Request, index: int) -> jax.Array:
        # Per-request stream, independent of slot placement and co-tenants.
        return jax.random.fold_in(jax.random.fold_in(self._base_key, req.uid), index)

    def _emit(self, slot: Slot, token: int, finished: bool) -> TokenEvent:
        req = slot.request
        index = len(req.generated_prefix) + len(slot.generated) - 1
        ev = TokenEvent(req.uid, token, index, finished, slot.index)
        now = self._clock()
        if req.first_token_time is None:
            if req.submit_time is not None:
                self._h_ttft.observe(now - req.submit_time)
            req.first_token_time = now
        elif req.last_token_time is not None:
            self._h_itl.observe(now - req.last_token_time)
        req.last_token_time = now
        self._m_tokens.inc()
        if self._on_token is not None:
            self._on_token(ev)
        return ev

    def _finish(self, slot: Slot) -> None:
        req = self.scheduler.retire(slot)
        self._frontend.pop(req.uid, None)
        if self.kv_layout == "paged":
            self.block_pool.release(req.uid)
            self._tables[slot.index, :] = SCRATCH_BLOCK
            self._dirty_tables.add(slot.index)
        self.pool = self._reset_slot(self.pool, slot.index)
        self._m_finished.inc()
        if self.tracer.enabled:
            self.tracer.instant("serve.finish", uid=req.uid,
                                tokens=len(self.scheduler.finished[req.uid]))
            self.tracer.async_end("request", req.uid)

    # -- paged-pool block management -----------------------------------------

    def _preempt(self, slot: Slot) -> None:
        """Evict ``slot``'s request (OOM policy): release its blocks back
        to the pool and requeue it at the front of the pending queue.  Its
        generated tokens fold into the request, so on re-admission it
        re-prefills ``prompt + generated_prefix`` and resumes mid-stream
        — greedy output and per-request PRNG streams are unaffected."""
        self._staging.pop(slot.index, None)  # drop any in-flight chunk state
        req = self.scheduler.preempt(slot)  # keeps FIFO priority
        # a victim bound this very tick but not yet prefilled owns no
        # blocks yet — nothing to release (staging slots may own adopted
        # prefix blocks, which this returns/unshares)
        if req.uid in self.block_pool.owners():
            self.block_pool.release(req.uid)
        self._tables[slot.index, :] = SCRATCH_BLOCK
        self._dirty_tables.add(slot.index)
        self.pool = self._reset_slot(self.pool, slot.index)
        self.preemptions += 1
        # queue-wait restarts for this stint — but only if the previous
        # stint was already observed at admission (enqueued_at consumed).
        # A victim preempted before its admission observe ran (bound this
        # very tick, then evicted by an earlier admission) still carries
        # its original stamp: restamping would silently drop that whole
        # wait stint from serve.queue_wait_s.
        if req.enqueued_at is None:
            req.enqueued_at = self._clock()
        self._m_preempted.inc()
        self.tracer.instant(
            "serve.preempt", uid=req.uid,
            generated=len(req.generated_prefix),
        )

    def _lowest_priority_victim(self, min_uid: int) -> Optional[Slot]:
        """The occupied slot with the largest uid above ``min_uid`` —
        latest-admitted work is evicted first (FIFO priority: earlier
        requests never yield to later ones).  Prefilling slots are fair
        game: staged chunk work is cheaper to redo than decoded tokens."""
        victims = [
            s for s in self.scheduler.occupied_slots if s.request.uid > min_uid
        ]
        return max(victims, key=lambda s: s.request.uid) if victims else None

    def _reclaim_blocks(self, n: int, min_uid: int) -> bool:
        """Make ``n`` blocks allocatable: evict cold prefix-trie leaves
        first (cached KV is cheaper to lose than live work), then preempt
        later-admitted slots.  False when neither can free enough."""
        while not self.block_pool.can_allocate(n):
            if self.prefix is not None and self.prefix.evict_one():
                continue
            victim = self._lowest_priority_victim(min_uid)
            if victim is None:
                return False
            self._preempt(victim)
        return True

    def _note_peak(self) -> None:
        """Record the allocator high-water mark at allocation time, so
        transients that release within the same tick still count."""
        self.peak_used_blocks = max(
            self.peak_used_blocks, self.block_pool.used_blocks
        )

    def _admit_blocks(self, slot: Slot, rows: int) -> bool:
        """Allocate the admission block table for ``rows`` prefill rows,
        preempting lower-priority slots on exhaustion.  Returns False (and
        requeues the request) if the pool cannot fit it even then."""
        req = slot.request
        n = (
            self._slot_blocks if self._ring
            else self.block_pool.blocks_for_tokens(rows)
        )
        if not self._reclaim_blocks(n, req.uid):
            self.scheduler.pending.appendleft(slot.release())
            return False
        blocks = self.block_pool.allocate(req.uid, n)
        self._tables[slot.index, :] = SCRATCH_BLOCK
        self._tables[slot.index, :n] = blocks
        self._dirty_tables.add(slot.index)
        self._note_peak()
        return True

    def _ensure_decode_block(self, slot: Slot) -> bool:
        """Grow the slot's table when this tick's KV write opens a new
        block (non-ring only; rings wrap in place).  Preempts on
        exhaustion — possibly the slot itself when it *is* the
        lowest-priority occupant.  Returns False if the slot was evicted."""
        if self._ring:
            return True
        rows = int(self._rows[slot.index])
        if rows % self.block_pool.block_size != 0:
            return True  # current block still has room
        req = slot.request
        while not self.block_pool.can_allocate(1):
            if self.prefix is not None and self.prefix.evict_one():
                continue
            victim = self._lowest_priority_victim(-1)
            if victim is None or victim is slot:
                self._preempt(slot)
                return False
            self._preempt(victim)
        blk = self.block_pool.append(req.uid)
        self._tables[slot.index, rows // self.block_pool.block_size] = blk
        self._dirty_tables.add(slot.index)
        self._note_peak()
        return True

    # -- chunked prefill + prefix cache (DESIGN.md §12) ------------------------

    def _staging_rows(self, rows: int) -> int:
        """Linear staging-cache capacity for a ``rows``-row prompt.

        Rings stage past the window (power of two >= max(rows, window+1))
        so chunks append linearly before ``finalize_ring_cache`` folds the
        buffer; non-ring paged staging matches the bucketed admission block
        grid exactly (same jit variants as the monolithic write); dense
        non-ring staging is the pool row itself."""
        if self._ring:
            need = max(rows, self.cfg.sliding_window + 1)
            ts = 1
            while ts < need:
                ts *= 2
            return ts
        if self.kv_layout == "paged":
            nb = bucket_blocks(
                self.block_pool.blocks_for_tokens(rows), self._slot_blocks
            )
            return nb * self.block_pool.block_size
        return self._cache_t

    def _admit_staging(self, slot: Slot) -> None:
        """Bind an admitted request to the chunked-prefill path: adopt any
        trie-cached prefix blocks (skipping their prefill outright), size
        the linear staging cache, and queue the uncached suffix for
        budgeted chunk processing (``_run_prefill_chunks``)."""
        req = slot.request
        fe = self._frontend.get(req.uid, {})
        tokens = np.concatenate(
            [req.prompt, np.asarray(req.generated_prefix, np.int32)]
        ) if req.generated_prefix else np.asarray(req.prompt, np.int32)
        rows = self._prefix_rows(fe) + len(tokens)
        p0, shared = 0, []
        if self.prefix is not None and not fe:
            # frontend prefixes (VLM patches) shift rows past the token
            # grid, so such requests never share — token-only lookups
            shared, p0 = self.prefix.lookup(tokens)
            if shared:
                self.block_pool.adopt(req.uid, shared)
        self._staging[slot.index] = {
            "req": req,
            "fe": fe,
            "tokens": tokens,
            "rows": rows,
            "p0": p0,
            "shared": list(shared),
            "suffix": tokens[p0:],
            "done": 0,
            "cache": None,
            "logits": None,
            "Ts": self._staging_rows(rows),
            "moe_cap": self.model.moe_prefill_capacity(rows),
        }
        slot.prefilling = True
        now = self._clock()
        if req.enqueued_at is not None:
            self._h_queue.observe(now - req.enqueued_at)
            req.enqueued_at = None  # consumed: a later preempt restamps
        self._m_admitted.inc()
        if self.tracer.enabled:
            self.tracer.instant(
                "serve.admit", uid=req.uid, slot=slot.index, rows=rows,
                prefix_rows=p0,
            )

    def _run_prefill_chunks(self) -> List[TokenEvent]:
        """Feed the tick's prompt-token budget through staging slots (FIFO
        by uid, power-of-two chunks); write completed prefills into the
        pool and sample their first token."""
        events: List[TokenEvent] = []
        budget = self.cb.prefill_chunk_tokens or (1 << 30)
        for idx in sorted(self._staging, key=lambda i: self._staging[i]["req"].uid):
            if budget <= 0:
                break
            st = self._staging.get(idx)
            if st is None:
                continue  # preempted by an earlier completion this tick
            req, suffix = st["req"], st["suffix"]
            while budget > 0 and st["done"] < len(suffix):
                c = min(len(suffix) - st["done"], budget)
                c = 1 << (int(c).bit_length() - 1)  # pow2: bounded variants
                chunk = suffix[st["done"]:st["done"] + c]
                with self.tracer.span(
                    "serve.prefill_chunk", uid=req.uid, tokens=int(c),
                    done=st["done"] + int(c), total=len(suffix),
                ):
                    if st["cache"] is None and st["p0"]:
                        # seed the staging buffer with the cached prefix
                        # rows straight out of the page pool — this is the
                        # prefill work the trie hit saves
                        st["cache"] = self.model.gather_prefix_cache(
                            self.pool, st["shared"], st["p0"], st["Ts"]
                        )
                    if st["cache"] is None:
                        st["logits"], st["cache"] = self.model.prefill(
                            self.params, jnp.asarray(chunk)[None],
                            self.cb.max_len, cache_t=st["Ts"],
                            moe_capacity=st["moe_cap"], **st["fe"]
                        )
                    else:
                        st["logits"], st["cache"] = self.model.prefill_extend(
                            self.params, st["cache"], jnp.asarray(chunk)[None],
                            moe_capacity=st["moe_cap"],
                        )
                self._m_h2d.inc(int(c) * 4)
                st["done"] += int(c)
                budget -= int(c)
            if st["done"] == len(suffix):
                ev = self._finish_prefill(idx)
                if ev is not None:
                    events.append(ev)
        return events

    def _strip_staging_cache(self, cache: PyTree) -> PyTree:
        """Drop chunk-only staging state (MoE queue counts) before the
        pool write — decode is stateless, exactly like the monolithic
        path."""
        return {
            "layers": {
                "k": cache["layers"]["k"], "v": cache["layers"]["v"],
            },
            "len": cache["len"],
            "pos": cache["pos"],
        }

    def _finish_prefill(self, idx: int) -> Optional[TokenEvent]:
        """Write a completed staging prefill into the pool, index its full
        blocks in the prefix trie, and sample the request's first token.
        Returns None when the pool could not fit the fresh blocks even
        after eviction/preemption (the request requeues, like the
        monolithic ``_admit_blocks`` failure path)."""
        st = self._staging.pop(idx)
        slot = self.scheduler.slots[idx]
        req, rows = st["req"], st["rows"]
        cache = st["cache"]
        if self.kv_layout == "paged":
            bp = self.block_pool
            if self._ring:
                n_real = n_fresh = self._slot_blocks  # rings never adopt
            else:
                n_real = bp.blocks_for_tokens(rows)
                n_fresh = n_real - len(st["shared"])
            if not self._reclaim_blocks(n_fresh, req.uid):
                self._requeue_staging(slot, st)
                return None
            if req.uid in bp.owners():  # adopted a prefix at admission
                fresh = [bp.append(req.uid) for _ in range(n_fresh)]
            else:
                fresh = bp.allocate(req.uid, n_fresh)
            table_row = st["shared"] + fresh
            self._tables[idx, :] = SCRATCH_BLOCK
            self._tables[idx, :n_real] = table_row
            self._dirty_tables.add(idx)
            self._note_peak()
            if self._ring:
                cache = self.model.finalize_ring_cache(cache, self._cache_t)
                write_table = table_row
            else:
                # the adopted prefix rows already live in the pool: scatter
                # them to scratch so the write cannot disturb shared blocks
                # (CoW discipline), and pad to the bucketed grid
                width = st["Ts"] // bp.block_size
                write_table = (
                    [SCRATCH_BLOCK] * len(st["shared"]) + fresh
                    + [SCRATCH_BLOCK] * (width - n_real)
                )
            if "moe" in cache["layers"]:
                cache = self._strip_staging_cache(cache)
            self.pool = self._write_slot_paged(
                self.pool, cache, idx, jnp.asarray(write_table, jnp.int32)
            )
            self._m_h2d.inc(len(write_table) * 4)
            self._rows[idx] = rows
            if self.prefix is not None and not st["fe"]:
                self.prefix.insert(st["tokens"], table_row)
        else:
            if self._ring:
                cache = self.model.finalize_ring_cache(cache, self._cache_t)
            elif "moe" in cache["layers"]:
                cache = self._strip_staging_cache(cache)
            self.pool = self._write_slot(self.pool, cache, idx)
        slot.prefilling = False
        self._m_d2h.inc(4)  # the admission-sampled token below
        tok = int(sample_token(
            st["logits"][0, -1],
            self._request_key(req, len(req.generated_prefix)),
            self.cfg, self._serve_cfg, guard=self.guard,
        ))
        finished = self.scheduler.record_token(slot, tok)
        ev = self._emit(slot, tok, finished)
        self._inputs[idx, 0] = tok
        if finished:
            self._finish(slot)
        return ev

    def _requeue_staging(self, slot: Slot, st: Dict[str, Any]) -> None:
        """Completion found no room even after eviction/preemption: drop
        the staged work and wait in line (the chunked counterpart of the
        monolithic ``_admit_blocks`` False path)."""
        req = st["req"]
        if req.uid in self.block_pool.owners():
            self.block_pool.release(req.uid)  # return adopted prefix blocks
        req.enqueued_at = self._clock()  # admission observed; new stint
        self.scheduler.pending.appendleft(slot.release())
        self._tables[slot.index, :] = SCRATCH_BLOCK
        self._dirty_tables.add(slot.index)
        self.pool = self._reset_slot(self.pool, slot.index)

    def kv_row_bytes(self) -> int:
        """Bytes one KV token row costs across all layers (K + V).

        Derived from the *actual* cache leaf dtypes — a quantized pool's
        int8/fp8 codes count one byte per element, not the compute dtype's
        four — so every byte figure downstream (kv_stats, benchmarks, CI's
        compression-ratio gate) reflects what the pool really stores.
        """
        layers = self.pool["layers"]
        num_layers = layers["k"].shape[0]
        per_head = int(np.prod(layers["k"].shape[-2:]))
        return num_layers * per_head * (
            layers["k"].dtype.itemsize + layers["v"].dtype.itemsize
        )

    def kv_scale_bytes_per_block(self) -> int:
        """Scale-page overhead per block across all layers (0 at fp32)."""
        layers = self.pool["layers"]
        if "k_scale" not in layers:
            return 0
        ks, vs = layers["k_scale"], layers["v_scale"]
        num_layers, _, hkv = ks.shape
        return num_layers * hkv * (ks.dtype.itemsize + vs.dtype.itemsize)

    def kv_stats(self) -> Dict[str, Any]:
        """Live KV-memory accounting (benchmarks/serve_throughput.py).

        ``kv_bytes_in_use`` is what an allocator has to *pin* right now:
        the dense layout pins its full ``num_slots * cache_len`` buffer
        regardless of occupancy; the paged layout pins only allocated
        blocks."""
        row_bytes = self.kv_row_bytes()
        if self.kv_layout == "paged":
            bs = self.block_pool.block_size
            prefix_stats = None
            if self.cb.prefix_cache:
                p = self.prefix
                prefix_stats = {
                    "hits": p.hits if p else 0,
                    "tokens_saved": p.tokens_saved if p else 0,
                    "evicted": p.evicted if p else 0,
                    "nodes": len(p) if p else 0,
                }
            # a block's full footprint: its token rows plus (quantized
            # layouts only) its per-(layer, head) scale rows
            block_bytes = bs * row_bytes + self.kv_scale_bytes_per_block()
            return {
                "prefix": prefix_stats,
                "layout": "paged",
                "kv_dtype": self.block_pool.kv_dtype,
                "used_blocks": self.block_pool.used_blocks,
                "free_blocks": self.block_pool.free_blocks,
                "total_blocks": self.block_pool.usable_blocks,
                # amortized storage cost of one cached token, scale pages
                # included — the benchmark/CI compression-ratio numerator
                "kv_bytes_per_token": block_bytes / bs,
                "kv_bytes_in_use": self.block_pool.used_blocks * block_bytes,
                "kv_bytes_capacity": (
                    self.block_pool.usable_blocks * block_bytes
                ),
                "peak_kv_bytes": self.peak_used_blocks * block_bytes,
                "preemptions": self.preemptions,
                "peak_used_blocks": self.peak_used_blocks,
                # counted decode traffic (ops.paged_gather_bytes): what
                # the resolved paged backend reads from the page pool —
                # gather adapters pay the full table window, pallas_paged
                # pays live pages only (DESIGN.md §11)
                "gather_bytes": self._m_gather.value(),
                "gather_bytes_per_token": (
                    self._m_gather.value() / max(self._m_tokens.value(), 1.0)
                ),
            }
        rows = self.cb.num_slots * self._cache_t
        return {
            "layout": "dense",
            "kv_dtype": "fp32",
            "kv_bytes_per_token": float(row_bytes),
            "kv_bytes_in_use": rows * row_bytes,
            "kv_bytes_capacity": rows * row_bytes,
            "peak_kv_bytes": rows * row_bytes,
        }

    def stats(self) -> Dict[str, Any]:
        """Engine-level counters: ticks, KV accounting, the engine's
        metrics-registry snapshot (request lifecycle histograms, queue /
        occupancy gauges, block-pool counters — DESIGN.md §10), and —
        when an accuracy guard is configured — its trip/fallback counters
        (calls / checks / trips / fallbacks / tripped / last_error)."""
        out: Dict[str, Any] = {"ticks": self.ticks, "kv": self.kv_stats()}
        out["guard"] = self.guard.stats() if self.guard is not None else None
        out["metrics"] = self.metrics.snapshot()
        return out

    # -- the tick (continued) ------------------------------------------------

    def step(self) -> List[TokenEvent]:
        """One engine tick: admit + prefill new requests (allocating KV
        blocks under the paged layout, preempting on exhaustion), then one
        jitted decode across the pool.  Returns the tokens emitted."""
        events: List[TokenEvent] = []
        paged = self.kv_layout == "paged"

        # 1. admission: prefill pending requests into free slots.  Decode
        #    state of already-active slots is untouched — they proceed on
        #    the same tick below.  A preempted request re-prefills its
        #    prompt plus everything it had generated.
        for slot in self.scheduler.admit():
            if slot.free:
                continue  # preempted by an earlier admission this tick
            if self._chunked:
                # staging path: prefix-cache lookup + budgeted chunk
                # prefill over the next ticks (DESIGN.md §12)
                self._admit_staging(slot)
                continue
            req = slot.request
            fe = self._frontend.get(req.uid, {})
            tokens = np.concatenate(
                [req.prompt, np.asarray(req.generated_prefix, np.int32)]
            ) if req.generated_prefix else req.prompt
            rows = self._prefix_rows(fe) + len(tokens)
            if paged:
                if not self._admit_blocks(slot, rows):
                    continue  # pool full even after preemption: wait in line
                # prefill only as many rows as the table holds: the block
                # grid, not max_len, sizes the single-request cache (rings
                # keep the full window — they wrap in place).  The width is
                # *bucketed* to the next power of two (serve.paged
                # .bucket_blocks): extra table entries point at scratch and
                # extra prefill rows are masked garbage, so the jitted
                # write_slot_paged compiles O(log W) variants under
                # mixed-length traffic instead of one per block count
                # (DESIGN.md §11; the slot index itself is traced)
                n_blocks = (
                    self._slot_blocks if self._ring
                    else bucket_blocks(
                        self.block_pool.blocks_for_tokens(rows),
                        self._slot_blocks,
                    )
                )
                prefill_len = (
                    self.cb.max_len if self._ring
                    else n_blocks * self.block_pool.block_size
                )
            else:
                prefill_len = self.cb.max_len
            now = self._clock()
            if req.enqueued_at is not None:
                self._h_queue.observe(now - req.enqueued_at)
                # consume the stamp: a preemption before the next admission
                # opens a NEW stint, and an unconsumed stamp marks a stint
                # that was never observed (see _preempt)
                req.enqueued_at = None
            self._m_admitted.inc()
            if self.tracer.enabled:
                self.tracer.instant("serve.admit", uid=req.uid,
                                    slot=slot.index, rows=rows)
            with self.tracer.span("serve.prefill", uid=req.uid, rows=rows):
                logits, cache1 = self.model.prefill(
                    self.params, jnp.asarray(tokens)[None], prefill_len, **fe
                )
                self._m_h2d.inc(len(tokens) * 4)
                if paged:
                    table = jnp.asarray(self._tables[slot.index, :n_blocks])
                    self._m_h2d.inc(n_blocks * 4)
                    self.pool = self._write_slot_paged(
                        self.pool, cache1, slot.index, table
                    )
                    self._rows[slot.index] = rows
                else:
                    self.pool = self._write_slot(self.pool, cache1, slot.index)
            self._m_d2h.inc(4)  # the admission-sampled token below
            tok = int(sample_token(
                logits[0, -1],
                self._request_key(req, len(req.generated_prefix)),
                self.cfg, self._serve_cfg, guard=self.guard,
            ))
            finished = self.scheduler.record_token(slot, tok)
            events.append(self._emit(slot, tok, finished))
            self._inputs[slot.index, 0] = tok
            if finished:
                self._finish(slot)

        # 1b. chunked prefill: stream this tick's prompt-token budget
        #     through staging slots; completed prefills join the decode
        #     batch below (same tick — with an infinite budget the timing
        #     matches the monolithic path exactly).
        if self._staging:
            events.extend(self._run_prefill_chunks())

        # 2. block upkeep: every active slot needs a home for this tick's
        #    KV write; exhaustion preempts latest-admitted work first.
        if paged:
            for slot in sorted(
                self.scheduler.active_slots, key=lambda s: s.request.uid
            ):
                if not slot.free:
                    self._ensure_decode_block(slot)

        # 3. one decode tick across the whole slot pool.
        active = self.scheduler.active_slots
        if active:
            # begin/end (not a span) keeps the long decode body unnested;
            # the uid list is only built when someone is recording
            if self.tracer.enabled:
                self.tracer.begin("serve.decode", tick=self.ticks,
                                  uids=[s.request.uid for s in active])
            s_count = self.cb.num_slots
            if paged:
                # flush dirty block-table rows: the only table bytes a
                # tick uploads (steady decode uploads none)
                for i in sorted(self._dirty_tables):
                    self._tables_dev = self._push_row(
                        self._tables_dev, jnp.int32(i),
                        jnp.asarray(self._tables[i]),
                    )
                    self._m_h2d.inc(self._slot_blocks * 4)
                self._dirty_tables.clear()
                tables = self._tables_dev
            else:
                tables = None
            if self._serve_cfg.temperature > 0.0:
                # full-pool uid/step vectors: free slots derive garbage
                # keys whose draws are discarded below
                uv = np.zeros(s_count, np.int32)
                sv = np.zeros(s_count, np.int32)
                for s in active:
                    uv[s.index] = s.request.uid
                    sv[s.index] = (
                        len(s.request.generated_prefix) + len(s.generated)
                    )
                uids, steps = jnp.asarray(uv), jnp.asarray(sv)
                self._m_h2d.inc(2 * s_count * 4)
            else:
                uids = steps = None
            # decode + sample fused in one program; ``last`` stays on
            # device unless the guard path needs it
            sampled_dev, last, self.pool = self._tick(
                self.params, self.pool, jnp.asarray(self._inputs),
                tables, uids, steps,
            )
            self.last_logits = last
            self._m_h2d.inc(self._inputs.size * 4)
            if paged:
                for slot in active:
                    self._rows[slot.index] += 1
            spec = self.cfg.softmax_spec
            if (
                self.guard is not None
                and self._serve_cfg.temperature > 0.0
                and self._serve_cfg.star_sampling
                and spec.kind != "exact"
            ):
                # guard needs concrete arrays: one batched eager softmax
                # over all active rows (a single oracle check per tick),
                # then the per-slot categorical draws — this path fetches
                # the logits row block, trading the single-transfer tick
                # for the host-side oracle comparison
                rows_ix = jnp.asarray([s.index for s in active])
                keys = jax.vmap(lambda u, i: jax.random.fold_in(
                    jax.random.fold_in(self._base_key, u), i))(
                        jnp.asarray([s.request.uid for s in active]),
                        jnp.asarray([
                            len(s.request.generated_prefix) + len(s.generated)
                            for s in active
                        ]))
                scaled = (
                    last[rows_ix].astype(jnp.float32)
                    / self._serve_cfg.temperature
                )
                probs = ops.softmax(scaled, spec, guard=self.guard)
                logp = jnp.log(jnp.maximum(probs, 1e-20))
                sampled = np.asarray(jax.vmap(
                    lambda k, lg: jax.random.categorical(k, lg, axis=-1)
                )(keys, logp)).astype(np.int32)
                self._m_d2h.inc(int(sampled.size) * 4 + len(active) * 4)
                toks = {s.index: int(t) for s, t in zip(active, sampled)}
            else:
                # the tick's single D2H transfer: the sampled-token vector
                sampled = np.asarray(sampled_dev)
                self._m_d2h.inc(int(sampled.size) * 4)
                toks = {s.index: int(sampled[s.index]) for s in active}
            if paged:
                impl = (
                    active_overrides("paged_attention").get("impl")
                    or self.cfg.paged_attention_spec.impl
                )
                pk = self.pool["layers"]["k"]
                quantized = "k_scale" in self.pool["layers"]
                self._m_gather.inc(pk.shape[0] * ops.paged_gather_bytes(
                    impl,
                    table_width=self._slot_blocks,
                    block_size=self.block_pool.block_size,
                    live_lens=np.minimum(self._rows, self._cache_t),
                    num_kv_heads=pk.shape[3],
                    head_dim=pk.shape[4],
                    dtype_bytes=pk.dtype.itemsize,
                    # per-layer K+V scale rows a quantized read touches
                    scale_bytes_per_block=(8 * pk.shape[3]) if quantized else 0,
                ))
            for slot in active:
                tok = toks[slot.index]
                finished = self.scheduler.record_token(slot, tok)
                events.append(self._emit(slot, tok, finished))
                self._inputs[slot.index, 0] = tok
                if finished:
                    self._finish(slot)
            if self.tracer.enabled:
                self.tracer.end("serve.decode")
            self.ticks += 1
        self._g_queue.set(len(self.scheduler.pending))
        self._g_active.set(len(self.scheduler.active_slots))
        self._g_jit.set(self.jit_cache_entries())
        if self.tracer.enabled:
            self.tracer.counter(
                "serve.sched",
                pending=len(self.scheduler.pending),
                active=len(self.scheduler.active_slots),
            )
            if paged:
                self.tracer.counter(
                    "kv.blocks", used=self.block_pool.used_blocks
                )
        return events

    # -- draining -----------------------------------------------------------

    def run(self, max_ticks: Optional[int] = None) -> Dict[int, List[int]]:
        """Drive ticks until every submitted request has finished; returns
        {uid: generated tokens}."""
        n = 0
        while not self.scheduler.done():
            self.step()
            n += 1
            if max_ticks is not None and n >= max_ticks and not self.scheduler.done():
                raise RuntimeError(f"engine did not drain within {max_ticks} ticks")
        return dict(self.scheduler.finished)

    def serve(
        self,
        prompts: Sequence[Sequence[int] | np.ndarray],
        max_new_tokens: int | Sequence[int],
        *,
        eos_id: Optional[int] = None,
    ) -> List[List[int]]:
        """Convenience: submit all prompts, drain, return outputs in order."""
        if isinstance(max_new_tokens, int):
            max_new_tokens = [max_new_tokens] * len(prompts)
        uids = [
            self.submit(p, int(m), eos_id=eos_id)
            for p, m in zip(prompts, max_new_tokens)
        ]
        done = self.run()
        return [done[u] for u in uids]
