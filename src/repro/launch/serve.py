"""Serving launcher: lockstep batch or continuous-batching generation.

  # lockstep (one fixed batch, synchronized decode)
  PYTHONPATH=src python -m repro.launch.serve --arch granite_8b --smoke \\
      --batch 4 --prompt-len 32 --gen 16

  # continuous batching (slot pool, staggered mixed-length requests)
  PYTHONPATH=src python -m repro.launch.serve --arch granite_8b --smoke \\
      --engine continuous --requests 8 --slots 4 --gen 16

  # paged KV cache (block-pool allocator; DESIGN.md §8)
  PYTHONPATH=src python -m repro.launch.serve --arch granite_8b --smoke \\
      --engine continuous --attn-impl paged --kv-block-size 16

  # shared-prefix KV cache + chunked prefill (DESIGN.md §12)
  PYTHONPATH=src python -m repro.launch.serve --arch granite_8b --smoke \\
      --engine continuous --kv-layout paged --prefix-cache \\
      --prefill-chunk-tokens 8

  # observability (DESIGN.md §10): Chrome trace + metrics snapshot
  PYTHONPATH=src python -m repro.launch.serve --arch granite_8b --smoke \\
      --engine continuous --trace-out trace.json --metrics-out metrics.json

Backend selection goes through the ``repro.ops`` registry: the config's
specs pick the defaults, ``--attn-impl`` / ``--softmax-impl`` retarget
every dispatch via ``ops.use(...)``, and Pallas interpret-vs-compile is
the platform's choice (``ops.default_interpret``) — the launcher no
longer flips any kernel flag by hand.

``--trace-out`` enables the global tracer for the run and writes the
Chrome trace-event JSON at exit (load it at https://ui.perfetto.dev);
``--metrics-out`` writes the merged metrics snapshot (engine registry +
process-global dispatch/guard counters).
"""

from __future__ import annotations

import argparse
import contextlib
import sys
import time


def _frontend_kwargs(cfg, rng, batch):
    import jax.numpy as jnp

    kw = {}
    if cfg.family == "vlm":
        kw["patch_embeds"] = jnp.asarray(
            rng.standard_normal((batch, cfg.num_patches, cfg.frontend_dim)), jnp.float32)
    if cfg.family == "encdec":
        kw["src_embeds"] = jnp.asarray(
            rng.standard_normal((batch, 64, cfg.frontend_dim or cfg.d_model)), jnp.float32)
    return kw


def _write_obs(args, engine=None) -> None:
    """Export the Chrome trace and/or metrics snapshot when requested."""
    import json

    from repro import obs

    if args.trace_out:
        tracer = obs.get_tracer()
        tracer.export_chrome(args.trace_out)
        print(f"wrote {len(tracer.events)} trace events to {args.trace_out} "
              f"(load in https://ui.perfetto.dev)")
    if args.metrics_out:
        snap = {"global": obs.default_registry().snapshot()}
        if engine is not None:
            snap["engine"] = engine.stats()
        with open(args.metrics_out, "w") as f:
            json.dump(snap, f, indent=2, default=float)
        print(f"wrote metrics snapshot to {args.metrics_out}")


def run_lockstep(args, cfg, params) -> int:
    import jax.numpy as jnp
    import numpy as np

    from repro import obs
    from repro.serve.engine import ServeConfig, ServeEngine

    max_len = args.max_len or (args.prompt_len + args.gen + cfg.num_patches + 8)
    eng = ServeEngine(cfg, params, ServeConfig(max_len=max_len, temperature=args.temperature))
    rng = np.random.default_rng(0)
    prompts = jnp.asarray(
        rng.integers(0, cfg.vocab_size, (args.batch, args.prompt_len)), jnp.int32)
    kw = _frontend_kwargs(cfg, rng, args.batch)

    t0 = time.perf_counter()
    with obs.get_tracer().span("serve.generate", batch=args.batch, gen=args.gen):
        toks, info = eng.generate(prompts, args.gen, **kw)
    dt = time.perf_counter() - t0
    print(f"generated {toks.shape} in {dt:.2f}s "
          f"({args.batch * args.gen / dt:.1f} tok/s) cache_len={info['cache_len']}")
    print("sample:", np.asarray(toks[0])[:16].tolist())
    _write_obs(args)
    return 0


@contextlib.contextmanager
def serving(cfg, cb_cfg=None, *, attn_impl=None, softmax_impl=None,
            params=None):
    """The serve path's set-up, shared by :func:`main` and ``chip_smoke.py``.

    Validates the config's specs against the registry (before any
    lowering), enters the ``ops.use`` backend overrides, materializes
    seeded random params unless ``params`` is given, and builds a
    :class:`~repro.serve.engine.ContinuousBatchingEngine` when ``cb_cfg``
    is given.  Yields ``(params, engine)``; the overrides stay active for
    the whole block, because the engine's programs are traced on first
    use.

    ``attn_impl="pallas_paged"`` is the gather-free paged decode kernel
    (DESIGN.md §11): it flips the serve stack to the block-pool cache and
    retargets the paged op, while dense invocations (prefill, lockstep)
    keep the ``paged`` marker's xla math.
    """
    import jax

    from repro import ops
    from repro.models.param import materialize
    from repro.models.registry import build_model
    from repro.serve.engine import ContinuousBatchingEngine

    kv_dtype = cb_cfg.kv_dtype if cb_cfg is not None else "fp32"
    overrides = {}
    if attn_impl == "pallas_paged":
        ops.validate(
            cfg.paged_attention_spec, impl="pallas_paged", kv_dtype=kv_dtype,
        )
        overrides["paged_attention"] = "pallas_paged"
        attn_impl = "paged"
    elif kv_dtype != "fp32":
        # quantized pages: fail at config time if the resolved paged
        # backend cannot dequantize this layout (DESIGN.md §13)
        ops.validate(cfg.paged_attention_spec, kv_dtype=kv_dtype)
    ops.validate(cfg.attention_spec, impl=attn_impl or cfg.attention_spec.impl)
    ops.validate(cfg.softmax_spec, impl=softmax_impl or cfg.softmax_spec.impl)
    if attn_impl:
        overrides["attention"] = attn_impl
    if softmax_impl:
        overrides["softmax"] = softmax_impl
    with ops.use(**overrides):
        if params is None:
            params = materialize(
                build_model(cfg).param_specs(), jax.random.PRNGKey(0))
        engine = (
            ContinuousBatchingEngine(cfg, params, cb_cfg)
            if cb_cfg is not None else None
        )
        yield params, engine


def run_continuous(args, cfg, eng) -> int:
    import numpy as np

    rng = np.random.default_rng(0)
    total = 0
    for i in range(args.requests):
        # mixed-length traffic: vary prompt and generation budgets
        plen = max(1, int(rng.integers(args.prompt_len // 2, args.prompt_len + 1)))
        gen = max(1, int(rng.integers(args.gen // 2, args.gen + 1)))
        kw = {}
        if cfg.family == "vlm":  # per-request stub patch embeddings
            kw["patch_embeds"] = rng.standard_normal(
                (1, cfg.num_patches, cfg.frontend_dim)).astype(np.float32)
        eng.submit(rng.integers(0, cfg.vocab_size, (plen,)), gen, **kw)
        total += gen

    t0 = time.perf_counter()
    done = eng.run()
    dt = time.perf_counter() - t0
    print(f"served {args.requests} requests / {total} tokens in {dt:.2f}s "
          f"({total / dt:.1f} tok/s) over {eng.ticks} decode ticks "
          f"({args.slots} slots, kv={eng.kv_layout})")
    if eng.kv_layout == "paged":
        st = eng.kv_stats()
        print(f"paged kv: peak {st['peak_used_blocks']}/{st['total_blocks']} "
              f"blocks ({st['peak_kv_bytes'] / 1e6:.2f} MB), "
              f"{st['preemptions']} preemptions")
        if st.get("prefix") is not None:
            p = st["prefix"]
            print(f"prefix cache: {p['hits']} hits, "
                  f"{p['tokens_saved']} prefill tokens saved, "
                  f"{p['evicted']} evicted ({p['nodes']} trie nodes)")
    lat = eng.metrics.histogram("serve.ttft_s")
    if lat.count():
        print(f"ttft p50={1e3 * lat.percentile(50):.1f}ms "
              f"p95={1e3 * lat.percentile(95):.1f}ms (n={lat.count()})")
    first = done[min(done)]
    print("sample:", first[:16])
    _write_obs(args, eng)
    return 0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--engine", choices=("lockstep", "continuous"), default="lockstep")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--requests", type=int, default=8, help="continuous: request count")
    ap.add_argument("--slots", type=int, default=4, help="continuous: KV slot pool size")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.8)
    ap.add_argument("--max-len", type=int, default=None)
    ap.add_argument(
        "--attn-impl", default=None, metavar="IMPL",
        help="force an attention backend (registry impl: reference|xla|pallas; "
        "'paged' additionally flips the continuous engine to the block-pool "
        "KV cache; 'pallas_paged' selects the block-pool cache AND routes "
        "decode through the gather-free scalar-prefetch kernel)",
    )
    ap.add_argument(
        "--kv-layout", choices=("dense", "paged"), default="dense",
        help="continuous-engine KV cache layout (--attn-impl paged also "
        "selects 'paged' via the ops override)",
    )
    ap.add_argument(
        "--kv-block-size", type=int, default=16,
        help="paged KV: tokens per cache block",
    )
    ap.add_argument(
        "--kv-pool-blocks", type=int, default=None,
        help="paged KV: usable blocks in the pool (default: dense-equivalent "
        "capacity slots * ceil(cache_len / block_size), where cache_len is "
        "max_len clamped to the arch's sliding window)",
    )
    ap.add_argument(
        "--kv-dtype", choices=("fp32", "int8", "fp8_e4m3"), default="fp32",
        help="paged KV: page-pool storage layout — int8/fp8_e4m3 store "
        "quantized codes plus per-(block, head) scale pages and decode "
        "dequantizes in-kernel (DESIGN.md §13); requires --kv-layout paged "
        "(or --attn-impl paged/pallas_paged)",
    )
    ap.add_argument(
        "--prefix-cache", action="store_true",
        help="continuous+paged: share KV blocks across requests with a "
        "common prompt prefix (radix trie over token-id block chunks; "
        "admission skips prefill for the cached prefix — DESIGN.md §12)",
    )
    ap.add_argument(
        "--prefill-chunk-tokens", type=int, default=None,
        help="continuous: budget of prompt tokens prefilled per tick; "
        "prompts stream through in power-of-two chunks interleaved with "
        "decode instead of head-of-line-blocking the pool",
    )
    ap.add_argument(
        "--softmax-impl", default=None, metavar="IMPL",
        help="force a softmax backend (registry impl: reference|xla|pallas)",
    )
    ap.add_argument(
        "--trace-out", default=None, metavar="PATH",
        help="enable tracing for the run and write Chrome trace-event JSON "
        "here (view at https://ui.perfetto.dev)",
    )
    ap.add_argument(
        "--metrics-out", default=None, metavar="PATH",
        help="write the metrics snapshot (engine registry + global "
        "dispatch/guard counters) as JSON",
    )
    args = ap.parse_args()

    from repro import obs
    from repro.configs import get_config, get_smoke_config
    from repro.launch.compile_cache import enable_compile_cache
    from repro.serve.engine import ContinuousConfig

    enable_compile_cache()
    if args.trace_out:
        # install before the engine is built — engines bind the global
        # tracer at construction
        obs.enable_tracing()
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    cb_cfg = None
    if args.engine == "continuous":
        cb_cfg = ContinuousConfig(
            num_slots=args.slots,
            max_len=args.max_len or (
                args.prompt_len + args.gen + cfg.num_patches + 8),
            temperature=args.temperature,
            kv_layout=args.kv_layout,
            kv_block_size=args.kv_block_size,
            kv_pool_blocks=args.kv_pool_blocks,
            kv_dtype=args.kv_dtype,
            prefix_cache=args.prefix_cache,
            prefill_chunk_tokens=args.prefill_chunk_tokens,
        )
    with serving(cfg, cb_cfg, attn_impl=args.attn_impl,
                 softmax_impl=args.softmax_impl) as (params, engine):
        if engine is not None:
            return run_continuous(args, cfg, engine)
        return run_lockstep(args, cfg, params)


if __name__ == "__main__":
    sys.exit(main())
