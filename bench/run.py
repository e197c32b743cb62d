"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload granite-8b-d8.chat --seed 7 \
        --seconds 40 --trace 0

Set-up builds the configuration's weights on the device from the seed,
the engine through the launcher's own ``serving()`` with the compiled
``pallas_paged`` decode kernel, and warms every program the cell's
traffic will run.  Then the traffic generator's requests are served for
``--seconds`` seconds, timed from their scheduled arrival.  ``--trace 1``
records a ``jax.profiler`` trace of the window and reports the per-layer
metrics instead of the end-to-end ones.  After the window the served
tokens of a sample of finished requests are checked against the plain
reference (``bench/correct``).

The last line of standard output is one JSON object; the numbers that
decided ``correct`` are printed beside their limits as the last lines of
standard error and under the result's last key, ``checks``.  The run
refuses any platform but TPU.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from types import ModuleType  # noqa: E402
from typing import Any, Callable, Dict, Optional, Sequence  # noqa: E402

sys.path[:0] = [str(Path(__file__).resolve().parents[1]),
                str(Path(__file__).resolve().parents[1] / "src")]

import numpy as np  # noqa: E402

from bench import common, e2e, trace_reduce, traffic_gen, warmup, weights  # noqa: E402
from bench.correct import compare  # noqa: E402
from bench.readings import Readings  # noqa: E402

TRACE_SECONDS = 15.0  # traced span of a --trace 1 window (at most)
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"

class CompileCount:
    """XLA compilations, from ``jax.monitoring``: every compile request
    records a backend-compile duration, and one that the persistent cache
    served also records a hit; the difference is what compiled."""

    def __init__(self):
        self.requests = 0
        self.hits = 0

    def duration(self, event: str, duration: float, **_) -> None:
        if event == BACKEND_COMPILE:
            self.requests += 1

    def event(self, event: str, **_) -> None:
        if event == CACHE_HIT:
            self.hits += 1

    def snapshot(self):
        return self.requests, self.hits


def parse_args(argv: Optional[Sequence[str]]) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None, metavar="DIR",
                    help="copy the traced window's .xplane.pb here")
    ap.add_argument("--rate", type=float, default=None,
                    help="override an open-loop mix's rate (knee sweeps only)")
    return ap.parse_args(argv)


def enable_compile_cache(jax) -> str:
    """The system's persistent compile cache, with every program put in,
    however fast it compiled, so a later run loads them all."""
    from repro.launch.compile_cache import enable_compile_cache as enable

    path = enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def system_config(config: Dict[str, Any], arch: ModuleType):
    """The system's ModelConfig for ``config``: the system's own config
    ``system_arch`` with every field that the architecture's ``FIELDS``
    names put in, then every key of the file's ``model`` group checked
    against the system, field by field.  A key that the table does not map
    stops the run."""
    from repro.configs import get_config

    model = config["model"]
    fields = {k: f for k, f in arch.FIELDS.items() if k in model}
    cfg = dataclasses.replace(get_config(config["system_arch"]), **{
        f: model[k] for k, f in fields.items() if isinstance(f, str)})
    got = {k: getattr(cfg, f) if isinstance(f, str) else f(cfg)
           for k, f in fields.items()}
    bad = {k: (v, got.get(k)) for k, v in model.items() if v != got.get(k)}
    if bad:
        raise SystemExit(f"the system does not run {config['name']} as stated: "
                         f"{bad} (file, system)")
    return cfg


def check_layout(cfg, params) -> None:
    """The benchmark's weight tree has the system's parameter shapes."""
    import jax
    from repro.models.param import shape_tree
    from repro.models.registry import build_model

    want = jax.tree.map(lambda s: (tuple(s.shape), str(s.dtype)),
                        shape_tree(build_model(cfg).param_specs()))
    got = jax.tree.map(lambda a: (tuple(a.shape), str(a.dtype)), params)
    if want != got:
        raise SystemExit("weight layout differs from the system's parameters")


def pool_blocks(cfg, engine: Dict[str, Any], dev) -> int:
    """The engine's ``kv_pool_blocks`` where the file fixes it; otherwise the
    blocks that fit beside the weights, keeping ``pool_headroom_bytes`` free
    for the programs' temporaries (as ``chip_smoke.py`` sizes it)."""
    import jax.numpy as jnp

    bs, kv_dtype = engine["kv_block_size"], engine["kv_dtype"]
    item = 1 if kv_dtype != "fp32" else jnp.dtype(cfg.compute_dtype).itemsize
    per_layer = 2 * bs * cfg.num_kv_heads * cfg.resolved_head_dim * item
    if kv_dtype != "fp32":
        per_layer += 2 * cfg.num_kv_heads * 4
    if "kv_pool_blocks" in engine:
        return engine["kv_pool_blocks"]
    mem = dev.memory_stats()
    if not mem:  # no allocator statistics (CPU): the dense equivalent
        return engine["num_slots"] * -(-engine["max_len"] // bs)
    free = mem["bytes_limit"] - mem["bytes_in_use"] - engine["pool_headroom_bytes"]
    return int(free // (per_layer * cfg.num_layers))


def find_xplane(root: str) -> Optional[str]:
    found = sorted(Path(root).rglob("*.xplane.pb"))
    return str(found[-1]) if found else None


def reference_check(ref: ModuleType, dims, params, client, mix, seed: int,
                    control: Optional[str] = None) -> Dict[str, Any]:
    """Per sampled request, the widest gap of its served tokens by the
    architecture's reference ``ref``; with ``control`` (a dtype) also the
    widest gap of the tokens that the reference computed in that lower
    precision, in the system's place, ranks first at the same positions."""
    finished = {r.uid: r for r in client.served.values() if r.finished}
    keys = compare.sample({k: {"tokens": r.tokens} for k, r in finished.items()}, seed)
    length = mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"]
    gaps, low = [], []
    for k in keys:
        rec = finished[k]
        prompt = client.requests[rec.index].prompt
        seq = np.zeros(length, np.int32)  # one length: one program
        full = np.concatenate([prompt, np.asarray(rec.tokens, np.int32)])
        seq[: len(full)] = full
        logits = np.asarray(ref.logits(params, seq, dims))
        gaps.append(float(np.max(compare.token_gaps(logits, len(prompt), rec.tokens))))
        if control is not None:
            lower = np.asarray(ref.logits(params, seq, dims, compute=control))
            low.append(float(np.max(compare.control_gaps(
                logits, lower, len(prompt), len(rec.tokens)))))
    return {"gaps": gaps, "control_gaps": low if control else None,
            "tokens": sum(len(finished[k].tokens) for k in keys)}


@dataclasses.dataclass
class Options:
    """What tests and the chip-side scripts change about a run: the chip
    check, the files a cell names (the configuration's architecture module
    among them), the control's precision, and a hook that gets the engine
    before it is warmed and served."""

    require_tpu: bool = True
    config: Optional[Dict[str, Any]] = None
    architecture: Optional[ModuleType] = None
    benchmark: Optional[Dict[str, Any]] = None
    mix: Optional[Dict[str, Any]] = None
    control: Optional[str] = None
    on_engine: Optional[Callable[[Any], None]] = None


def run_cell(args: argparse.Namespace, opts: Options) -> Optional[Dict[str, Any]]:
    """One run of a cell; None (and a message) where the machine does not
    have what the cell needs."""
    bench = opts.benchmark or common.load_benchmark()
    cell = common.find_cell(bench, args.workload)
    config = opts.config or common.load_config(cell["config"])
    arch = opts.architecture or common.reference_module(config["reference"])
    mix = dict(opts.mix or common.load_traffic(cell["traffic"]))
    if args.rate is not None:
        mix["rate_per_s"] = args.rate

    import jax

    devices = jax.devices()
    dev = devices[0]
    if opts.require_tpu and dev.platform != "tpu":
        print(f"bench/run.py: needs a TPU, JAX found platform {dev.platform!r}",
              file=sys.stderr)
        return None
    if len(devices) < cell["chips"]:
        print(f"bench/run.py: {cell['name']} needs {cell['chips']} chips, "
              f"JAX found {len(devices)}", file=sys.stderr)
        return None
    enable_compile_cache(jax)
    compiles = CompileCount()
    jax.monitoring.register_event_duration_secs_listener(compiles.duration)
    jax.monitoring.register_event_listener(compiles.event)

    from bench import peaks as peaks_mod
    from bench.drive import Client
    from repro import obs
    from repro.launch.serve import serving
    from repro.serve.engine import ContinuousConfig

    chip_peaks = peaks_mod.peaks(dev.device_kind if opts.require_tpu else "TPU v5 lite")
    cfg = system_config(config, arch)
    dims = arch.dims_of(config["model"])
    wseed = int(np.random.default_rng([args.seed, 1]).integers(1 << 31))
    params = weights.make_params(arch.layout(dims), wseed,
                                 config["model"]["param_dtype"])
    jax.block_until_ready(params)
    check_layout(cfg, params)

    engine = config["engine"]
    cb = ContinuousConfig(
        num_slots=engine["num_slots"], max_len=engine["max_len"],
        temperature=engine["temperature"], kv_layout="paged",
        kv_block_size=engine["kv_block_size"],
        kv_pool_blocks=pool_blocks(cfg, engine, dev),
        kv_dtype=engine["kv_dtype"], prefix_cache=engine["prefix_cache"],
        prefill_chunk_tokens=engine["prefill_chunk_tokens"])
    requests = traffic_gen.generate(mix, seed=args.seed, seconds=args.seconds,
                                    vocab_size=dims["V"])
    if args.trace:
        obs.enable_tracing(capacity=1 << 20)  # the engine binds it when built
    else:
        obs.disable_tracing()
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if args.trace else None
    seconds = min(args.seconds, TRACE_SECONDS) if args.trace else args.seconds

    with serving(cfg, cb, attn_impl="pallas_paged", params=params) as (_, eng):
        if opts.on_engine is not None:
            opts.on_engine(eng)
        n_prefill = warmup.warm_prefill(eng, warmup.prefill_shapes(eng, mix))
        warmup.warm_engine(eng, mix, dims["V"], args.seed)
        kind = common.traffic_kind(mix["kind"])
        client = Client(eng, requests, kind, cb.num_slots)
        client.start(time.perf_counter())
        kind.lead_in(client, mix)
        if args.trace:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0  # host: the bench.* spans, not every call
            jax.profiler.start_trace(trace_dir, profiler_options=options)
            eng.tracer.clear()
        t0 = time.perf_counter()
        setup_s = t0 - T_START
        c0 = compiles.snapshot()
        n0, pending0 = len(client.steps), len(eng.scheduler.pending)
        client.run(t0 + seconds)
        t1 = t0 + seconds
        c1 = compiles.snapshot()
        pending1 = len(eng.scheduler.pending)
        window_steps = client.steps[n0:]
        if args.trace:
            chunks = [e for e in eng.tracer.events if e.name == "serve.prefill_chunk"]
            jax.block_until_ready(eng.pool)
            jax.profiler.stop_trace()
        mem = dev.memory_stats() or {}
        preemptions = eng.preemptions
        for rec in client.served.values():  # the engine's record agrees
            done = eng.scheduler.finished.get(rec.uid)
            if done is not None and list(done) != rec.tokens:
                raise SystemExit(f"request {rec.uid}: events and the "
                                 "engine's finished tokens differ")
        del eng
        client.eng = None
    gc.collect()  # the pool is gone before the reference runs

    window_compiles = (c1[0] - c0[0]) - (c1[1] - c0[1])
    served = list(client.served.values())
    ttft, censored = e2e.ttft(served, t0, t1)
    half = [r for r in served if t0 <= r.due < t0 + seconds / 2]
    values = {
        "ttft_p95_s": e2e.p95(ttft),
        "itl_p95_s": e2e.p95(e2e.itl(served, t0, t1)),
        "output_tokens_per_s": e2e.output_tokens(served, t0, t1) / seconds,
        "setup_s": setup_s,
    }
    # every request the window owed work: sent before the close and not
    # finished before the open
    attempted = sum(1 for r in served if r.due < t1
                    and not (r.finished and r.times[-1] < t0))

    limit = config["correct"]["token_gap_limit"]
    check = reference_check(arch, dims, params, client, mix, args.seed,
                            opts.control)
    # the control is judged as the system is, in its place
    judged = check["gaps"] if opts.control is None else check["control_gaps"]
    widest = max(judged) if judged else None
    served_tokens = sum(len(r.tokens) for r in served if r.finished)
    checks = {
        "token_gap": {"value": widest, "limit": limit},
        "sampled_tokens": {"value": check["tokens"],
                           "limit": min(compare.SAMPLE_TOKENS, served_tokens)},
        "window_compiles": {"value": window_compiles, "limit": 0},
    }
    correct = (widest is not None and widest <= limit
               and check["tokens"] >= checks["sampled_tokens"]["limit"]
               and window_compiles == 0)

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": int(mem.get("peak_bytes_in_use", 0))}
    line: Dict[str, Any] = {"correct": bool(correct), "attempted": int(attempted),
                            "failed": sum(1 for g in judged if g > limit)}
    if args.trace:
        path = find_xplane(trace_dir)
        if args.keep_trace and path:
            os.makedirs(args.keep_trace, exist_ok=True)
            shutil.copy(path, os.path.join(args.keep_trace, Path(path).name))
        tr = trace_reduce.load(path) if path else trace_reduce.Trace([], [], [])
        shutil.rmtree(trace_dir, ignore_errors=True)
        busy = trace_reduce.union(tr.ops)
        spans = [s for s in tr.spans if s[0].startswith("bench.")]
        a = spans[0][1] if spans else 0.0
        b = spans[-1][2] if spans else 0.0
        act = 2 if cfg.compute_dtype == "bfloat16" else 4
        ctx = Readings(
            dims=dims, num_slots=cb.num_slots,
            kv_itemsize=1 if cb.kv_dtype != "fp32" else act, act_itemsize=act,
            peaks=chip_peaks, steps=window_steps,
            counters={"prefill_tokens": sum(e.args["tokens"] for e in chunks)},
            trace=tr)
        metrics = {}
        for m in common.cell_metrics(bench, cell["name"], "per_layer"):
            v = common.metric_reader(m["name"]).read(ctx)
            if v is not None and math.isfinite(v):
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device["busy_s"] = trace_reduce.covered(busy, a, b)
        device["window_s"] = b - a
        line["metrics"] = metrics
        line["device"] = device
        line["breakdown"] = {
            "device_ops": trace_reduce.top_ops(tr.ops),
            "idle_gaps": trace_reduce.idle_gaps(busy, spans, a, b)}
    else:
        line["metrics"] = {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in common.cell_metrics(bench, cell["name"], "end_to_end")}
        line["device"] = device
    line["checks"] = checks
    info = {"requests_due": attempted, "ttft_censored": censored,
            "first_half_finished": (sum(r.finished for r in half) / len(half)
                                    if half else None),
            "pending_at_open": pending0, "pending_at_close": pending1,
            "window_ticks": len(window_steps), "prefill_programs": n_prefill,
            "preemptions": preemptions, "pool_blocks": cb.kv_pool_blocks,
            "window_cache_loads": c1[1] - c0[1],
            "system_gaps": check["gaps"], "control_gaps": check["control_gaps"],
            "e2e": values}
    return {"line": line, "info": info}


def main(argv: Optional[Sequence[str]] = None, opts: Optional[Options] = None) -> int:
    out = run_cell(parse_args(argv), opts or Options())
    if out is None:
        return 2
    print("info: " + json.dumps(out["info"]), file=sys.stderr)
    for name, c in out["line"]["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})", file=sys.stderr)
    print(json.dumps(out["line"]), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
