"""Serving throughput: lockstep vs continuous vs paged-KV continuous.

A Poisson arrival trace of mixed-length requests is served three ways:

* **lockstep** — requests are grouped into fixed batches of ``slots`` in
  arrival order; each batch prefills together (prompts right-padded to the
  batch max) and decodes for the batch max generation budget.  Every
  request pays for the longest member of its batch, and a batch cannot
  start until its last member has arrived.
* **continuous** — the slot-pool engine admits each request as it arrives
  (1 engine tick = 1 time unit of the trace) and retires it the moment its
  own budget is done, so lanes never idle on a co-tenant's schedule.
* **paged** — the same continuous engine over the block-pool KV cache
  (DESIGN.md §8): memory is allocated in ``kv_block_size``-token blocks as
  requests grow, so peak KV bytes track *live tokens* instead of
  ``slots * max_len``.  Greedy decode is token-identical to the dense
  path, so steps/makespan match and the delta is purely memory.

Views, printed as ``name,value,derived`` CSV (benchmarks/run.py idiom):

1. ``decode_steps`` — pool-wide decode steps executed (device work).
   Prefill passes are reported separately on each line.
2. ``makespan`` — completion time in trace units (1 decode step = 1 unit,
   prefill = 1 unit), *including* arrival waits: the latency picture.
3. ``toks_per_s`` — measured wall-clock useful tokens/sec (CPU smoke:
   host dispatch dominates; treat as a liveness check).
4. ``peak_kv_bytes`` — what an allocator must pin: the dense engines pin
   their full pool; the paged engine pins its peak allocated blocks.
   Per-tick block-pool occupancy lands in the ``--json`` record so
   BENCH_*.json can track memory as well as speed.
5. ``ttft`` / ``itl`` — per-request latency percentiles (p50/p95/p99,
   wall seconds) sourced from the engine's obs histograms
   (``serve.ttft_s`` / ``serve.itl_s`` / ``serve.queue_wait_s``,
   DESIGN.md §10), printed for the continuous engines and embedded in
   the ``--json`` record under ``latency``.
6. ``prefix_tokens_saved`` — a second, shared-prefix trace (every prompt
   opens with the same 16 tokens) served by the paged engine with
   ``prefix_cache`` + chunked prefill (DESIGN.md §12).  Reports the
   fraction of prefill tokens skipped via the radix trie (asserted
   ≥ 30%), token parity against the uncached paged run, and makespan
   parity on the original *disjoint* trace (the cache must not slow
   down traffic that cannot share).  Lands in ``--json`` under
   ``prefix``.

    PYTHONPATH=src python -m benchmarks.serve_throughput [--json out.json]
"""

from __future__ import annotations

import argparse
import json

import numpy as np

from benchmarks._timing import Stopwatch


def make_trace(n_requests: int, rng: np.random.Generator, *, rate: float = 0.8):
    """Poisson arrivals (exp inter-arrival, ``rate`` per tick) of requests
    with uniformly mixed prompt lengths and generation budgets."""
    t = 0.0
    trace = []
    for _ in range(n_requests):
        t += rng.exponential(1.0 / rate)
        trace.append({
            "arrival": t,
            "prompt_len": int(rng.integers(4, 24)),
            "gen": int(rng.integers(4, 16)),
        })
    return trace


def _latency_percentiles(eng):
    """TTFT / ITL / queue-wait percentiles (wall seconds) read from the
    engine's obs histograms (DESIGN.md §10) — the benchmark reports what
    the metrics layer measured, not a separately hand-rolled list."""
    out = {}
    for name, key in (("serve.ttft_s", "ttft"), ("serve.itl_s", "itl"),
                      ("serve.queue_wait_s", "queue_wait")):
        h = eng.metrics.histogram(name)
        out[key] = {"count": h.count(), "p50": h.percentile(50),
                    "p95": h.percentile(95), "p99": h.percentile(99)}
    return out


def run_lockstep(cfg, params, trace, prompts, slots, max_len):
    import jax.numpy as jnp

    from repro.serve.engine import ServeConfig, ServeEngine

    eng = ServeEngine(cfg, params, ServeConfig(max_len=max_len, temperature=0.0))
    useful = steps = prefills = 0
    clock = 0.0  # trace-time: batch starts after its last arrival
    with Stopwatch() as sw:
        for i in range(0, len(trace), slots):
            batch = trace[i:i + slots]
            bp = prompts[i:i + slots]
            plen = max(r["prompt_len"] for r in batch)
            gen = max(r["gen"] for r in batch)
            # right-pad prompts to the batch max (lockstep needs one shape)
            mat = np.zeros((len(batch), plen), np.int32)
            for j, p in enumerate(bp):
                mat[j, :len(p)] = p
            eng.generate(jnp.asarray(mat), gen)
            useful += sum(r["gen"] for r in batch)
            steps += gen - 1  # token 0 of each batch comes from the prefill
            prefills += 1
            clock = max(clock, max(r["arrival"] for r in batch)) + 1 + (gen - 1)
    return {"engine": "lockstep", "tokens": useful, "steps": steps,
            "prefills": prefills, "makespan": clock, "wall": sw.seconds}


def run_continuous(cfg, params, trace, prompts, slots, max_len, *,
                   kv_layout="dense", kv_block_size=16, kv_pool_blocks=None,
                   prefix_cache=False, prefill_chunk_tokens=None,
                   kv_dtype="fp32"):
    from repro.serve.engine import ContinuousBatchingEngine, ContinuousConfig

    eng = ContinuousBatchingEngine(
        cfg, params,
        ContinuousConfig(num_slots=slots, max_len=max_len,
                         kv_layout=kv_layout, kv_block_size=kv_block_size,
                         kv_pool_blocks=kv_pool_blocks,
                         prefix_cache=prefix_cache,
                         prefill_chunk_tokens=prefill_chunk_tokens,
                         kv_dtype=kv_dtype))
    useful = 0
    occupancy = []  # per-tick allocated blocks (paged) for the JSON record
    outputs = {}
    i = 0
    tick = 0
    with Stopwatch() as sw:
        while i < len(trace) or not eng.scheduler.done():
            while i < len(trace) and trace[i]["arrival"] <= tick:
                eng.submit(prompts[i], trace[i]["gen"],
                           arrival_time=trace[i]["arrival"])
                useful += trace[i]["gen"]
                i += 1
            eng.step()
            if eng.kv_layout == "paged":
                occupancy.append(eng.block_pool.used_blocks)
            tick += 1
    outputs.update(eng.scheduler.finished)
    st = eng.kv_stats()
    # each preemption re-admission runs one extra prefill pass
    prefills = len(trace) + st.get("preemptions", 0)
    out = {"engine": f"continuous[{eng.kv_layout}]", "tokens": useful,
           "steps": eng.ticks, "prefills": prefills,
           "makespan": float(tick), "wall": sw.seconds,
           "util": useful / max(eng.ticks * slots, 1),
           "peak_kv_bytes": st["peak_kv_bytes"],
           "kv_bytes_capacity": st["kv_bytes_capacity"],
           "latency": _latency_percentiles(eng),
           "outputs": outputs}
    if eng.kv_layout == "paged":
        out["block_occupancy_per_tick"] = occupancy
        out["peak_used_blocks"] = st["peak_used_blocks"]
        out["total_blocks"] = st["total_blocks"]
        out["preemptions"] = st["preemptions"]
        out["kv_block_size"] = kv_block_size
        out["prefix"] = st.get("prefix")
        # quantized-layout accounting (DESIGN.md §13): amortized storage
        # cost of one cached token, scale pages included
        out["kv_dtype"] = st["kv_dtype"]
        out["kv_bytes_per_token"] = st["kv_bytes_per_token"]
    return out


def main(n_requests: int = 12, slots: int = 4, kv_block_size: int = 16,
         json_path: str | None = None,
         kv_dtypes: tuple = ("fp32", "int8", "fp8_e4m3")):
    import jax

    from repro.configs import get_smoke_config
    from repro.models.param import materialize
    from repro.models.registry import build_model

    cfg = get_smoke_config("granite_8b")
    model = build_model(cfg)
    params = materialize(model.param_specs(), jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    trace = make_trace(n_requests, rng)
    prompts = [rng.integers(0, cfg.vocab_size, (r["prompt_len"],)).astype(np.int32)
               for r in trace]
    max_len = 24 + 16 + 8  # prompt + gen + headroom

    lk = run_lockstep(cfg, params, trace, prompts, slots, max_len)
    print(f"serve_lockstep_decode_steps,{lk['steps']},"
          f"prefills={lk['prefills']} makespan={lk['makespan']:.0f} "
          f"toks_per_s={lk['tokens'] / lk['wall']:.1f}")

    cb = run_continuous(cfg, params, trace, prompts, slots, max_len)
    print(f"serve_continuous_decode_steps,{cb['steps']},"
          f"prefills={cb['prefills']} makespan={cb['makespan']:.0f} "
          f"toks_per_s={cb['tokens'] / cb['wall']:.1f} "
          f"slot_util={cb['util']:.2f}")
    for key in ("ttft", "itl"):
        p = cb["latency"][key]
        print(f"serve_continuous_{key}_p50_ms,{p['p50'] * 1e3:.2f},"
              f"p95={p['p95'] * 1e3:.2f} p99={p['p99'] * 1e3:.2f} "
              f"n={p['count']} source=obs_histograms")

    pg = run_continuous(cfg, params, trace, prompts, slots, max_len,
                        kv_layout="paged", kv_block_size=kv_block_size)
    print(f"serve_paged_decode_steps,{pg['steps']},"
          f"prefills={pg['prefills']} makespan={pg['makespan']:.0f} "
          f"toks_per_s={pg['tokens'] / pg['wall']:.1f} "
          f"peak_blocks={pg['peak_used_blocks']}/{pg['total_blocks']} "
          f"preemptions={pg['preemptions']}")

    print(f"serve_continuous_step_speedup,{lk['steps'] / cb['steps']:.2f}x,"
          f"device_decode_work requests={n_requests} slots={slots}")
    print(f"serve_continuous_makespan_speedup,"
          f"{lk['makespan'] / cb['makespan']:.2f}x,trace_time_incl_arrivals")
    # the paged deltas: memory strictly below dense at parity makespan.
    # Parity is a hard invariant (DESIGN.md §8) — fail loudly, don't just
    # print, so scripted runs catch a paged-vs-dense divergence.
    parity = all(pg["outputs"][u] == cb["outputs"][u] for u in cb["outputs"])
    assert parity, "paged greedy output diverged from the dense engine"
    print(f"serve_paged_kv_bytes_vs_dense,"
          f"{pg['peak_kv_bytes'] / cb['peak_kv_bytes']:.2f}x,"
          f"peak {pg['peak_kv_bytes']} vs dense {cb['peak_kv_bytes']} bytes")
    print(f"serve_paged_makespan_parity,"
          f"{cb['makespan'] / pg['makespan']:.2f}x,"
          f"token_parity={parity}")

    # --- shared-prefix trace: radix-trie KV reuse + chunked prefill ---
    # every prompt opens with the same 16 tokens, block size 4, chunk
    # budget 8 tokens/tick (DESIGN.md §12).  The cached run must be
    # token-identical to the uncached paged run and skip a substantial
    # fraction of prefill work.
    sp_rng = np.random.default_rng(1)
    sp_trace = make_trace(n_requests, sp_rng)
    shared = sp_rng.integers(0, cfg.vocab_size, (16,)).astype(np.int32)
    sp_prompts = [
        np.concatenate([shared, sp_rng.integers(
            0, cfg.vocab_size, (r["prompt_len"],)).astype(np.int32)])
        for r in sp_trace]
    sp_max_len = 16 + 24 + 16 + 8  # prefix + prompt + gen + headroom
    sp_base = run_continuous(cfg, params, sp_trace, sp_prompts, slots,
                             sp_max_len, kv_layout="paged", kv_block_size=4)
    sp = run_continuous(cfg, params, sp_trace, sp_prompts, slots, sp_max_len,
                        kv_layout="paged", kv_block_size=4,
                        prefix_cache=True, prefill_chunk_tokens=8)
    sp_parity = all(sp["outputs"][u] == sp_base["outputs"][u]
                    for u in sp_base["outputs"])
    assert sp_parity, "prefix-cached greedy output diverged from uncached paged"
    total_prompt_tokens = sum(len(p) for p in sp_prompts)
    saved = sp["prefix"]["tokens_saved"]
    frac = saved / total_prompt_tokens
    print(f"serve_prefix_tokens_saved,{saved},"
          f"fraction={frac:.2f} hits={sp['prefix']['hits']} "
          f"of {total_prompt_tokens} prompt tokens (shared-prefix trace, "
          f"block=4 chunk=8) token_parity={sp_parity}")
    assert frac >= 0.30, (
        f"prefix cache saved only {frac:.0%} of prefill tokens (need >=30%)")

    # --- kv_dtype sweep: quantized page pools (DESIGN.md §13) ---
    # the same trace served at each KV storage layout; fp32 reuses the
    # paged run above.  The record keeps bytes/token (scale pages
    # included) and the peak pool footprint — CI asserts the int8 row
    # compresses to <= 0.55x fp32 from this JSON.
    kv_sweep = {}
    for kvd in kv_dtypes:
        r = pg if kvd == "fp32" else run_continuous(
            cfg, params, trace, prompts, slots, max_len,
            kv_layout="paged", kv_block_size=kv_block_size, kv_dtype=kvd)
        kv_sweep[kvd] = {
            "kv_bytes_per_token": r["kv_bytes_per_token"],
            "peak_kv_bytes": r["peak_kv_bytes"],
            "peak_used_blocks": r["peak_used_blocks"],
            "makespan": r["makespan"],
        }
        print(f"serve_paged_kv_bytes_per_token[{kvd}],"
              f"{r['kv_bytes_per_token']:.0f},"
              f"peak_kv_bytes={r['peak_kv_bytes']} "
              f"peak_blocks={r['peak_used_blocks']}")
    if "fp32" in kv_sweep and "int8" in kv_sweep:
        ratio = (kv_sweep["int8"]["kv_bytes_per_token"]
                 / kv_sweep["fp32"]["kv_bytes_per_token"])
        print(f"serve_paged_kv_compression_int8,{ratio:.3f}x,"
              f"bytes_per_token_vs_fp32 (target <=0.55)")

    # disjoint trace: the cache must not cost anything when nothing is
    # shared — same arrivals as the paged baseline, prefix cache on
    dp = run_continuous(cfg, params, trace, prompts, slots, max_len,
                        kv_layout="paged", kv_block_size=kv_block_size,
                        prefix_cache=True)
    dp_parity = all(dp["outputs"][u] == pg["outputs"][u]
                    for u in pg["outputs"])
    assert dp_parity, "prefix-cache engine diverged on the disjoint trace"
    assert dp["makespan"] <= pg["makespan"], (
        f"prefix cache regressed disjoint-trace makespan: "
        f"{dp['makespan']} > {pg['makespan']}")
    print(f"serve_prefix_disjoint_makespan_parity,"
          f"{pg['makespan'] / dp['makespan']:.2f}x,"
          f"token_parity={dp_parity} (no regression when nothing shares)")

    if json_path:
        record = {
            "bench": "serve_throughput",
            "requests": n_requests,
            "slots": slots,
            "max_len": max_len,
            "lockstep": lk,
            "continuous": cb,
            "paged": pg,
            "paged_token_parity": parity,
            "kv_dtype_sweep": kv_sweep,
            "prefix": {
                "tokens_saved": saved,
                "hits": sp["prefix"]["hits"],
                "evicted": sp["prefix"]["evicted"],
                "saved_fraction": frac,
                "total_prompt_tokens": total_prompt_tokens,
                "shared_trace_token_parity": sp_parity,
                "shared_trace_makespan": sp["makespan"],
                "shared_trace_makespan_uncached": sp_base["makespan"],
                "disjoint_token_parity": dp_parity,
                "disjoint_makespan": dp["makespan"],
                "disjoint_makespan_uncached": pg["makespan"],
            },
        }
        for eng_rec in (cb, pg, sp, sp_base, dp):
            eng_rec.pop("outputs", None)  # token lists stay out of the record
        with open(json_path, "w") as f:
            json.dump(record, f, indent=2, default=float)
        print(f"wrote {json_path}")
    return True


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--kv-block-size", type=int, default=16)
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write the full record (incl. per-tick block-pool "
                    "occupancy) as JSON")
    ap.add_argument("--kv-dtype", default="all",
                    choices=("fp32", "int8", "fp8_e4m3", "all"),
                    help="KV storage layout(s) for the paged kv_dtype "
                    "sweep (default: all three)")
    args = ap.parse_args()
    dtypes = (("fp32", "int8", "fp8_e4m3") if args.kv_dtype == "all"
              else ("fp32", args.kv_dtype)
              if args.kv_dtype != "fp32" else ("fp32",))
    main(args.requests, args.slots, args.kv_block_size, args.json,
         kv_dtypes=dtypes)
