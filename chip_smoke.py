"""Smoke run of the continuous-batching serve path on one TPU chip.

    python chip_smoke.py              # one chip: serve phases A and B
    python chip_smoke.py --chips 4    # four chips: sharded QAT steps only

The model is granite-8b at its published widths (d_model 4096, 32/8 heads,
d_ff 14336, vocab 49152, float32 params, bfloat16 compute) cut from 36 to
8 layers, the depth whose float32 weights (8.6 GB) leave room for a KV
pool on a 16 GB v5e.  Weights are random from a seed.  Everything runs in
this one process, through the launcher's own set-up
(``repro.launch.serve.serving``): continuous batching, then the paged KV
pool, then ``repro.ops`` dispatch, then the compiled Pallas decode kernel.

* Phase A: fp32 pages (stored in the compute dtype), 8 slots, block 16,
  16 requests with 128-1024-token prompts and 32-64 new tokens.
* Phase B: int8 pages, shared-prefix cache and 256-token prefill chunks,
  over requests that share a 512-token prefix.

Checks, each of which fails the run: every request returns its whole
token budget with ids in range; the compiled ``pallas_paged`` kernel
agrees with the ``reference`` gather backend on the live pool; the decode
logits of two requests per phase agree with a float32 full forward pass
(``reference`` attention, ``highest`` matmul precision); no Pallas
dispatch ran in interpret mode.  The two parity checks also read faults
planted on the same data and fail unless each lands past their limit.
The last line of standard output is the JSON result; it is printed only
when every check passed on a TPU.

Prompt lengths are powers of two so that the number of prefill programs
to compile stays small; JAX's persistent compilation cache
(``repro.launch.compile_cache``) keeps them for the next run.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time
from typing import Dict, List, Optional, Sequence, Tuple

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import obs, ops  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.serve import serving  # noqa: E402
from repro.models.param import count_params, materialize  # noqa: E402
from repro.models.registry import build_model  # noqa: E402
from repro.serve.engine import ContinuousConfig  # noqa: E402

ARCH = "granite_8b"
SERVE_LAYERS = 8  # of 36: the deepest cut whose f32 weights leave a KV pool
TRAIN_LAYERS = 2  # the four-chip QAT steps
SLOTS, BLOCK = 8, 16
# HBM kept free of the KV pool.  The v5e compiler's memory analysis bounds
# the temporaries at 3.5 GB for the decode tick and 3.8 GB for a 1024-token
# prefill at these widths; on a v5e at most 1.83 GB besides the weights and
# the pool was live at the peak (PERF.md section 5).
HEADROOM_BYTES = 5 << 30
# Kernel vs the gather oracle on the same pages: relative L2 error of each
# live slot's output [Hq, D] against the oracle's, worst slot.  The two
# differ by float32 rounding, one bf16 rounding of the probabilities on the
# MXU (2^-9 relative) and, on int8 pages, whose dequantized keys are not
# bf16 values, rare one-level flips of the STAR grid: about 1.1e-3 on a
# v5e.  One wrong page in each slot reads 6.6e-3 there in the int8 phase
# (slots of 40-64 pages) and 0.21 in the bf16 one, so the limit sits
# between.  Each run plants such faults and requires the check to see
# them (``check_kernel_on_pool``).
KERNEL_TOL = 3e-3
# Engine decode logits (bfloat16 compute, as the config states) vs a
# float32 forward pass: relative L2 error per position, worst position.
# bf16 keeps 8 mantissa bits (2^-9 relative per rounding); the residual
# stream and every matmul output round once per layer, and STAR's 0.25-wide
# score grid turns some of those roundings into one-level probability
# flips: 9.3e-3 on a v5e.  Float8 (e4m3) compute reads 0.40 there, and
# each run requires the check to see it (``check_logits``).  With random
# weights at these widths the logits of neighbouring positions differ by
# only 1.1e-2, so this check cannot see a wrong position; the kernel check
# on the live pool is the one that sees a wrong page or head.
LOGITS_TOL = 5e-2
PALLAS_IMPLS = ("pallas", "pallas_paged", "hwmodel")


@dataclasses.dataclass(frozen=True)
class Phase:
    """One serving phase: the pool layout and the traffic it sees."""

    name: str
    kv_dtype: str
    prompt_lens: Tuple[int, ...]  # one per request, shared prefix included
    gens: Tuple[int, ...]  # new tokens per request
    shared_prefix: int = 0  # leading tokens every prompt shares
    prefix_cache: bool = False
    prefill_chunk_tokens: Optional[int] = None
    track: Tuple[int, int] = (0, 1)  # requests whose logits are checked

    @property
    def max_len(self) -> int:
        return max(self.prompt_lens) + max(self.gens)


def chip_phases(seed: int = 0) -> Tuple[Phase, Phase]:
    rng = np.random.default_rng(seed)
    a_lens = tuple(int(x) for x in rng.permutation([128, 256, 512, 1024] * 4))
    b_lens = tuple(512 + int(x) for x in rng.choice([128, 256, 512], 16))
    return (
        Phase("A", "fp32", a_lens,
              tuple(int(x) for x in rng.integers(32, 65, 16)), track=(0, 15)),
        Phase("B", "int8", b_lens,
              tuple(int(x) for x in rng.integers(32, 65, 16)),
              shared_prefix=512, prefix_cache=True, prefill_chunk_tokens=256,
              track=(1, 15)),
    )


def serve_config(num_layers: int = SERVE_LAYERS):
    """granite-8b at published widths, only the depth cut."""
    return dataclasses.replace(get_config(ARCH), num_layers=num_layers)


def pool_blocks_that_fit(cfg, kv_dtype: str, free_bytes: int) -> int:
    """Blocks of a ``kv_dtype`` page pool that fit in ``free_bytes``."""
    item = 1 if kv_dtype != "fp32" else jnp.dtype(cfg.compute_dtype).itemsize
    per_layer = 2 * BLOCK * cfg.num_kv_heads * cfg.resolved_head_dim * item
    if kv_dtype != "fp32":
        per_layer += 2 * cfg.num_kv_heads * 4  # K and V scale rows
    return int(free_bytes // (per_layer * cfg.num_layers))


def make_prompts(cfg, phase: Phase, seed: int) -> List[np.ndarray]:
    rng = np.random.default_rng(seed)
    prefix = rng.integers(0, cfg.vocab_size, phase.shared_prefix)
    return [
        np.concatenate([prefix, rng.integers(0, cfg.vocab_size,
                                             n - phase.shared_prefix)])
        .astype(np.int32)
        for n in phase.prompt_lens
    ]


class CompileClock:
    """Sums XLA backend-compile seconds (``jax.monitoring`` events)."""

    def __init__(self):
        self.seconds = 0.0

    def __call__(self, event: str, duration: float, **_) -> None:
        if event.endswith("backend_compile_duration"):
            self.seconds += duration


def require_separation(what: str, readings: Dict[str, float],
                       tol: float) -> None:
    """Raise unless ``readings["error"]`` is within ``tol`` and every
    planted fault's reading lies past it (a check that cannot see the
    fault it is there for fails too)."""
    err = readings["error"]
    if not np.isfinite(err) or err > tol:
        raise AssertionError(f"{what}: error {err:.3e} > {tol} ({readings})")
    blind = [k for k, v in readings.items() if k != "error" and not v > tol]
    if blind:
        raise AssertionError(
            f"{what}: planted faults {blind} read within {tol} ({readings}); "
            "the check cannot see them")


def worst_slot_error(out, ref, live) -> float:
    """Worst relative L2 error over the live slots' ``[Hq, D]`` outputs."""
    out = np.asarray(out, np.float32)[live].reshape(len(live), -1)
    ref = np.asarray(ref, np.float32)[live].reshape(len(live), -1)
    rel = (np.linalg.norm(out - ref, axis=-1)
           / np.maximum(np.linalg.norm(ref, axis=-1), 1e-30))
    return float(np.max(rel))


def check_kernel_on_pool(eng, cfg, kv_dtype: str,
                         seed: int) -> Dict[str, float]:
    """``pallas_paged`` on the engine's live pool stack and block tables,
    reading its last layer in place as the tick does, vs the ``reference``
    gather backend on that layer at ``highest`` precision.  Returns the
    worst-slot error of the kernel as it runs ("error") and with a fault
    planted: each live slot's last full page replaced by the next live
    slot's ("page"), and every query head reading its neighbour's KV head
    ("head").  Raises unless the first is within ``KERNEL_TOL`` and the
    others past it."""
    stack = eng.pool["layers"]
    layer = cfg.num_layers - 1
    quantized = kv_dtype != "fp32"
    lens = np.zeros(eng.cb.num_slots, np.int32)
    for slot in eng.scheduler.active_slots:
        lens[slot.index] = eng._rows[slot.index]
    live = np.flatnonzero(lens)
    if len(live) < 2:
        raise RuntimeError("kernel check needs two live slots")
    tables = np.asarray(eng._tables)
    valid = jnp.asarray(lens)
    group = cfg.num_heads // cfg.num_kv_heads
    q = jax.random.normal(
        jax.random.PRNGKey(seed),
        (eng.cb.num_slots, 1, cfg.num_heads, cfg.resolved_head_dim),
    ).astype(cfg.compute_dtype).astype(jnp.float32)  # the values q holds

    def run(impl, q, tables, k, v, kv_scales, layer):
        spec = ops.PagedAttentionSpec(
            impl=impl, softmax=cfg.softmax_spec, block_size=BLOCK,
            kv_dtype=kv_dtype)
        return ops.paged_attention(q, k, v, jnp.asarray(tables), spec,
                                   kv_valid_len=valid, layer=jnp.int32(layer),
                                   kv_scales=kv_scales)

    scales = (stack["k_scale"], stack["v_scale"]) if quantized else None

    def kernel(q, tables):
        return run("pallas_paged", q, tables, stack["k"], stack["v"], scales,
                   layer)

    with jax.default_matmul_precision("highest"):
        if quantized:
            ref = run("reference", q, tables, stack["k"], stack["v"], scales,
                      layer)
        else:  # the oracle computes in float32 on the pages' values
            ref = run("reference", q, tables,
                      stack["k"][layer][None].astype(jnp.float32),
                      stack["v"][layer][None].astype(jnp.float32), None, 0)
    # planted fault 1: the page before each slot's last live one (full, and
    # the slot's own past a shared prefix) swapped for another slot's
    wrong = tables.copy()
    last_full = np.maximum((lens - 1) // BLOCK - 1, 0)
    for i, s in enumerate(live):
        t = live[(i + 1) % len(live)]
        wrong[s, last_full[s]] = tables[t, last_full[t]]
    # planted fault 2: GQA head mix-up, query head h reads KV head h//g + 1
    mixed = jnp.roll(kernel(jnp.roll(q, group, axis=2), tables), -group, axis=2)
    readings = {
        "error": worst_slot_error(kernel(q, tables), ref, live),
        "page": worst_slot_error(kernel(q, wrong), ref, live),
        "head": worst_slot_error(mixed, ref, live),
    }
    require_separation("pallas_paged vs reference on the live pool",
                       readings, KERNEL_TOL)
    return readings


def run_phase(cfg, params, phase: Phase, *, pool_blocks: int,
              slots: int = SLOTS, seed: int = 0,
              clock: Optional[CompileClock] = None) -> Dict[str, object]:
    """Serve ``phase`` through the launcher's set-up with the gather-free
    decode kernel; check budgets and the kernel on the live pool.  Returns
    the phase report, with the tracked requests' tokens and decode logits
    for :func:`check_logits`."""
    prompts = make_prompts(cfg, phase, seed)
    cb = ContinuousConfig(
        num_slots=slots, max_len=phase.max_len, temperature=0.0,
        kv_layout="paged", kv_block_size=BLOCK, kv_pool_blocks=pool_blocks,
        kv_dtype=phase.kv_dtype, prefix_cache=phase.prefix_cache,
        prefill_chunk_tokens=phase.prefill_chunk_tokens,
    )
    compile0 = clock.seconds if clock is not None else 0.0
    t0 = time.perf_counter()
    with serving(cfg, cb, attn_impl="pallas_paged", params=params) as (_, eng):
        uids = [eng.submit(p, g) for p, g in zip(prompts, phase.gens)]
        tracked = {uids[i]: {} for i in phase.track}
        kernel = None
        step_s = []  # host clock per tick; each ends in the sampled tokens' D2H
        while not eng.scheduler.done():
            t1 = time.perf_counter()
            events = eng.step()
            step_s.append(time.perf_counter() - t1)
            for ev in events:
                if ev.uid in tracked and ev.index >= 1:
                    tracked[ev.uid][ev.index] = np.asarray(
                        eng.last_logits[ev.slot], np.float32)
            if kernel is None and len(eng.scheduler.active_slots) == slots:
                kernel = check_kernel_on_pool(eng, cfg, phase.kv_dtype, seed)
        done = eng.run()
        if kernel is None:
            raise AssertionError("the pool never held every slot live")
        stats = eng.kv_stats()
        preemptions = eng.preemptions
        del eng
    wall = time.perf_counter() - t0
    for uid, prompt, gen in zip(uids, prompts, phase.gens):
        toks = done[uid]
        if len(toks) != gen:
            raise AssertionError(
                f"phase {phase.name}: request {uid} returned {len(toks)} of "
                f"{gen} tokens")
        if min(toks) < 0 or max(toks) >= cfg.vocab_size:
            raise AssertionError(f"phase {phase.name}: token id out of range")
    if preemptions:
        raise AssertionError(
            f"phase {phase.name}: {preemptions} preemptions; the logit check "
            "reads decode rows only")
    return {
        "phase": phase.name,
        "kv_dtype": phase.kv_dtype,
        "requests": len(uids),
        "tokens_served": int(sum(len(done[u]) for u in uids)),
        "pool_blocks": pool_blocks,
        "peak_used_blocks": stats["peak_used_blocks"],
        "prefix_tokens_saved": (stats["prefix"] or {}).get("tokens_saved", 0),
        "wall_s": wall,
        "compile_s": (clock.seconds - compile0) if clock is not None else None,
        "ticks": len(step_s),
        "tick_p50_s": float(np.median(step_s)),
        "kernel": kernel,
        "tracked": [
            {"tokens": np.concatenate([prompts[i], done[uids[i]]]),
             "prompt_len": len(prompts[i]),
             "logits": tracked[uids[i]]}
            for i in phase.track
        ],
    }


def check_logits(cfg, params, tracked: Sequence[dict]) -> Dict[str, float]:
    """Decode logits vs a float32 full forward pass with ``reference``
    attention at ``highest`` matmul precision.  Returns the worst relative
    L2 error per position ("error") and the same reading for logits of a
    forward pass in float8 (e4m3) compute in place of the engine's
    ("float8").  Raises unless the first is within ``LOGITS_TOL`` and the
    second past it."""
    length = max(len(t["tokens"]) for t in tracked)

    def forward(compute_dtype):
        """Full-sequence logits of each tracked request, padded to one
        length (causal: padding leaves earlier rows untouched, and one
        length compiles one program); vocabulary padding columns cut."""
        model = build_model(dataclasses.replace(cfg, compute_dtype=compute_dtype))
        fn = jax.jit(model.forward)
        rows = []
        for t in tracked:
            toks = np.zeros(length, np.int32)
            toks[: len(t["tokens"])] = t["tokens"]
            rows.append(np.asarray(fn(params, jnp.asarray(toks)[None])[0],
                                   np.float32)[:, :cfg.vocab_size])
        return rows

    with ops.use(attention="reference"), jax.default_matmul_precision("highest"):
        ref, low = forward("float32"), forward("float8_e4m3fn")

    def worst(got, want):
        rel = (np.linalg.norm(got - want, axis=-1)
               / np.maximum(np.linalg.norm(want, axis=-1), 1e-30))
        return float(np.max(rel)) if np.all(np.isfinite(rel)) else np.inf

    readings = {"error": 0.0, "float8": 0.0}
    for t, r, f in zip(tracked, ref, low):
        # the logits at row p-1+i predict generated token i
        idx = sorted(t["logits"])
        rows = np.asarray([t["prompt_len"] - 1 + i for i in idx])
        got = np.stack([t["logits"][i] for i in idx])[:, :cfg.vocab_size]
        readings["error"] = max(readings["error"], worst(got, r[rows]))
        readings["float8"] = max(readings["float8"], worst(f[rows], r[rows]))
    require_separation("decode logits vs the float32 forward", readings,
                       LOGITS_TOL)
    return readings


def pallas_dispatches() -> List[dict]:
    """The process's Pallas dispatches: op, impl and resolved interpret."""
    calls = obs.default_registry().counter("ops.dispatch.calls").snapshot()
    return [c["labels"] for c in calls
            if c["labels"].get("impl") in PALLAS_IMPLS]


def serve_main(seed: int) -> None:
    clock = CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock)
    cfg = serve_config()
    dev = jax.devices()[0]
    print(f"model: {ARCH} layers {cfg.num_layers} of 36 (depth cut only), "
          f"d_model {cfg.d_model}, heads {cfg.num_heads}/{cfg.num_kv_heads}, "
          f"d_ff {cfg.d_ff}, vocab {cfg.vocab_size}, params {cfg.param_dtype}, "
          f"compute {cfg.compute_dtype}", flush=True)
    t0 = time.perf_counter()
    model = build_model(cfg)
    params = materialize(model.param_specs(), jax.random.PRNGKey(seed))
    jax.block_until_ready(params)
    weight_bytes = sum(x.nbytes for x in jax.tree.leaves(params))
    print(f"weights: {count_params(model.param_specs())} params, "
          f"{weight_bytes} bytes, init {time.perf_counter() - t0:.1f}s",
          flush=True)
    parity = []
    for phase in chip_phases(seed):
        gc.collect()  # the last phase's pool must be gone before sizing
        mem = dev.memory_stats()
        free = mem["bytes_limit"] - mem["bytes_in_use"] - HEADROOM_BYTES
        blocks = pool_blocks_that_fit(cfg, phase.kv_dtype, free)
        rep = run_phase(cfg, params, phase, pool_blocks=blocks, seed=seed,
                        clock=clock)
        parity.append(rep.pop("tracked"))
        mem = dev.memory_stats()
        rep["peak_bytes_in_use"] = mem["peak_bytes_in_use"]
        rep["bytes_limit"] = mem["bytes_limit"]
        print(f"phase {phase.name}: " + json.dumps(rep), flush=True)
    gc.collect()  # free the last pool before the float32 forward passes
    for phase, tracked in zip(chip_phases(seed), parity):
        readings = check_logits(cfg, params, tracked)
        print(f"phase {phase.name} logits over {len(tracked)} requests "
              f"(tolerance {LOGITS_TOL}): " + json.dumps(readings), flush=True)
    dispatches = pallas_dispatches()
    print("pallas dispatches: " + json.dumps(dispatches), flush=True)
    if not any(d["impl"] == "pallas_paged" for d in dispatches):
        raise AssertionError("no pallas_paged dispatch: the kernel never ran")
    if any(d["interpret"] for d in dispatches):
        raise AssertionError(
            "a Pallas dispatch resolved interpret=True on the chip "
            f"(REPRO_OPS_INTERPRET={os.environ.get('REPRO_OPS_INTERPRET')!r})")


def train_main(seed: int, cfg=None, batch: int = 8, seq_len: int = 512) -> None:
    """Three QAT steps (STAR softmax with the straight-through backward) of
    granite-8b, full width, depth 2, on a (data, model) = (1, 4) mesh; the
    step-0 loss must match a one-device forward of the same params and
    batch.  ``cfg`` replaces the model for a rehearsal on virtual CPU
    devices."""
    from repro.data.synthetic import make_batch
    from repro.launch.mesh import make_mesh
    from repro.train.loop import LoopConfig, run_train
    from repro.train.step import TrainConfig

    if len(jax.devices()) < 4:
        raise RuntimeError(f"--chips 4 needs 4 devices, found {len(jax.devices())}")
    cfg = dataclasses.replace(
        cfg or get_config(ARCH), num_layers=TRAIN_LAYERS, softmax_kind="star_ste")
    loop = LoopConfig(num_steps=3, batch=batch, seq_len=seq_len, seed=seed,
                      log_every=1)
    print(f"model: {ARCH} layers {cfg.num_layers} of 36, full widths, "
          f"softmax {cfg.softmax_spec.kind}, batch {loop.batch}x{loop.seq_len}",
          flush=True)
    model = build_model(cfg)
    batch0 = {k: jnp.asarray(v) for k, v in make_batch(
        cfg, batch=loop.batch, seq_len=loop.seq_len, step=0).items()}
    params = materialize(model.param_specs(), jax.random.PRNGKey(seed))
    ref_loss = float(jax.jit(model.loss)(params, batch0))
    del params
    print(f"one-device step-0 loss: {ref_loss:.6f}", flush=True)
    mesh = make_mesh((1, 4), ("data", "model"))
    t0 = time.perf_counter()
    res = run_train(cfg, TrainConfig(total_steps=3, warmup_steps=1), loop,
                    mesh=mesh)
    wall = time.perf_counter() - t0
    devs = mesh.devices.ravel().tolist()
    held = {d: 0 for d in devs}
    for leaf in jax.tree.leaves(res["state"]["params"]):
        for shard in leaf.addressable_shards:
            held[shard.device] += shard.data.nbytes
    held = [held[d] for d in devs]
    print(f"param bytes per device: {held}", flush=True)
    if devs[0].memory_stats() is not None:
        print("bytes in use per device: "
              f"{[d.memory_stats()['bytes_in_use'] for d in devs]}", flush=True)
    losses = [h["loss"] for h in res["history"]]
    print(f"sharded losses: {losses} ({wall:.1f}s, compile included)",
          flush=True)
    # same math and dtype on one device and on four: only the order of
    # the partitioned reductions differs; a missing or doubled partial sum
    # moves the loss by a factor, not a fraction of a percent
    rel = abs(losses[0] - ref_loss) / abs(ref_loss)
    print(f"step-0 loss relative difference: {rel:.3e} (tolerance 2e-3)",
          flush=True)
    if not np.isfinite(losses).all() or rel > 2e-3:
        raise AssertionError("sharded step-0 loss disagrees with one device")
    if max(held) > 2 * min(held):
        raise AssertionError("the weights are not spread over the devices")


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded QAT steps on four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    devices = jax.devices()
    dev = devices[0]
    print(f"platform {dev.platform}, device_kind {dev.device_kind}, "
          f"devices {len(devices)}", flush=True)
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, found platform {dev.platform!r}",
              file=sys.stderr)
        return 2
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    if args.chips == 4:
        train_main(args.seed)
    else:
        serve_main(args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
