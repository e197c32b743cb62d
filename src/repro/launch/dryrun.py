import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# a CPU tool: pinned off any attached chip, for itself and the per-cell
# children it spawns (they inherit the environment)
os.environ["JAX_PLATFORMS"] = "cpu"

"""Multi-pod dry-run: lower + compile every (arch x shape) on the production
mesh; record memory/cost analysis + roofline terms.

MUST be run as its own process (the lines above lock jax to 512 host CPU
devices before any other import — never set that flag globally).  The
production mesh it compiles for is made of TPU v5e chips, whose peaks the
roofline terms use.

  python -m repro.launch.dryrun --arch granite_8b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all --mesh both --out experiments/dryrun
"""

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402
from typing import Any, Dict, Optional  # noqa: E402

TARGET_DEVICE_KIND = "TPU v5 lite"  # the production mesh's chips


def _probe_depths(cfg) -> tuple:
    """(d1, d2, full_layers) for the unrolled cost probes."""
    if cfg.family == "hybrid":
        period = len(cfg.block_pattern)
        return period, 2 * period, cfg.num_layers
    return 1, 2, cfg.num_layers


def _with_depth(cfg, k: int):
    import dataclasses

    if cfg.family == "encdec":
        return dataclasses.replace(cfg, num_layers=k, num_decoder_layers=k)
    return dataclasses.replace(cfg, num_layers=k)


def _run_cell(arch: str, shape_name: str, mesh_kind: str, out_dir: Optional[str],
              rules_override: Optional[Dict[str, Any]] = None,
              tag: str = "", microbatches: int = 1,
              probes: bool = True, moments_dtype: str = "float32",
              cfg_overrides: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    import jax
    from jax.sharding import NamedSharding

    from repro.configs import SHAPES, get_config
    from repro.core.scan_ctl import unroll_scans
    from repro.distributed.sharding import (
        DEFAULT_RULES, logical_to_pspec, param_shardings, use_mesh_rules,
    )
    from repro.launch.mesh import make_production_mesh
    from repro.launch.roofline import (
        active_param_count, collective_bytes, model_flops, roofline_terms,
    )
    from repro.launch.specs import input_specs
    from repro.models.param import count_params
    from repro.models.registry import build_model
    from repro.train.state import state_specs
    from repro.train.step import TrainConfig, make_train_step

    t_start = time.time()
    cfg = get_config(arch)
    if cfg_overrides:
        cfg = dataclasses.replace(cfg, **cfg_overrides)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    chips = mesh.size
    n_params = count_params(build_model(cfg).param_specs())

    rules = dict(DEFAULT_RULES)
    # big models: spread FSDP across the pod axis too, or optimizer state
    # alone blows the 16 GB/chip budget (llama3-405b)
    if n_params * 10 / 256 > 13e9:
        rules["embed"] = ("pod", "data")
    if rules_override:
        rules.update({k: tuple(v) if isinstance(v, (list, tuple)) else (v,)
                      for k, v in rules_override.items()})

    def lower_and_compile(cfg_k):
        """Build the cell's step fn for cfg_k; lower + compile on the mesh."""
        model = build_model(cfg_k)
        pspecs = model.param_specs()
        kind, inputs = input_specs(cfg_k, shape)

        def shard_of(spec_tree):
            return param_shardings(spec_tree, rules, mesh)

        def batch_sharding(tree):
            def one(sds):
                axes = ["batch"] + [None] * (len(sds.shape) - 1)
                return NamedSharding(mesh, logical_to_pspec(axes, sds.shape, rules, mesh))
            return jax.tree.map(one, tree)

        with use_mesh_rules(mesh, rules):
            if kind == "train":
                from repro.optim.adamw import AdamWConfig
                tc = TrainConfig(microbatches=microbatches,
                                 adamw=AdamWConfig(moments_dtype=moments_dtype))
                step = make_train_step(model, tc)
                st_spec = state_specs(pspecs, tc.adamw)
                fn = jax.jit(
                    step,
                    in_shardings=(shard_of(st_spec), batch_sharding(inputs["batch"])),
                    out_shardings=(shard_of(st_spec), None),
                    donate_argnums=(0,),
                )
                from repro.models.param import shape_tree as _st
                args = (_st(st_spec), inputs["batch"])
            elif kind == "prefill":
                max_len = inputs.pop("_max_len")
                frontend_keys = [k for k in inputs if k not in ("params", "tokens")]

                def prefill_fn(params, tokens, *front):
                    kw = dict(zip(frontend_keys, front))
                    return model.prefill(params, tokens, max_len, **kw)

                fn = jax.jit(
                    prefill_fn,
                    in_shardings=(
                        shard_of(pspecs),
                        batch_sharding(inputs["tokens"]),
                        *(batch_sharding(inputs[k]) for k in frontend_keys),
                    ),
                )
                args = (inputs["params"], inputs["tokens"],
                        *(inputs[k] for k in frontend_keys))
            else:  # decode
                if cfg_k.family == "encdec":
                    from repro.launch.specs import ENCDEC_DECODE_SRC_LEN
                    cache_spec = model.cache_spec(
                        shape.global_batch, shape.seq_len, src_len=ENCDEC_DECODE_SRC_LEN
                    )
                else:
                    cache_spec = model.cache_spec(shape.global_batch, shape.seq_len)
                fn = jax.jit(
                    model.decode_step,
                    in_shardings=(
                        shard_of(pspecs), shard_of(cache_spec),
                        batch_sharding(inputs["tokens"]),
                    ),
                )
                args = (inputs["params"], inputs["cache"], inputs["tokens"])
            t0 = time.time()
            lowered = fn.lower(*args)
            compiled = lowered.compile()
            return kind, compiled, time.time() - t0

    def costs_of(compiled):
        out = {"flops": 0.0, "bytes": 0.0, "coll": 0.0, "coll_detail": {}}
        ca = compiled.cost_analysis()
        out["flops"] = float(ca.get("flops", 0.0))
        out["bytes"] = float(ca.get("bytes accessed", 0.0))
        coll = collective_bytes(compiled.as_text())
        out["coll"] = float(coll["total"])
        out["coll_detail"] = coll
        return out

    # ---- full-depth scanned compile: memory truth + compile-health ----------
    kind, compiled, t_full = lower_and_compile(cfg)
    rec: Dict[str, Any] = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind, "step": kind,
        "chips": chips, "ok": True, "tag": tag, "n_params": n_params,
        "compile_s": round(t_full, 2), "microbatches": microbatches,
    }
    try:
        ma = compiled.memory_analysis()
        for field in (
            "argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes",
        ):
            rec[field] = int(getattr(ma, field, 0))
        rec["peak_bytes_per_dev"] = int(
            getattr(ma, "argument_size_in_bytes", 0)
            + getattr(ma, "temp_size_in_bytes", 0)
        )
    except Exception as e:  # pragma: no cover
        rec["memory_analysis_error"] = str(e)
    scanned_costs = costs_of(compiled)
    rec["scanned_flops_per_dev"] = scanned_costs["flops"]  # undercounted (scan)
    del compiled

    # ---- unrolled depth probes: exact per-layer costs, extrapolated ----------
    # XLA cost_analysis counts a scan body once regardless of trip count, so
    # FLOPs/bytes/collectives come from unrolled d1/d2-layer probes:
    #   full = C1 + (L - d1)/(d2 - d1) * (C2 - C1)
    d1, d2, l_full = _probe_depths(cfg)
    if not probes:
        rec["flops_per_dev"] = scanned_costs["flops"]
        rec["bytes_per_dev"] = scanned_costs["bytes"]
        rec["coll_bytes_per_dev"] = scanned_costs["coll"]
        rec["collectives"] = scanned_costs["coll_detail"]
        rec["probes"] = False
    elif True:
      try:
        with unroll_scans():
            _, c1, t1 = lower_and_compile(_with_depth(cfg, d1))
            p1 = costs_of(c1)
            del c1
            _, c2, t2 = lower_and_compile(_with_depth(cfg, d2))
            p2 = costs_of(c2)
            del c2
        scale = (l_full - d1) / (d2 - d1)
        rec["probe_compile_s"] = round(t1 + t2, 2)
        rec["flops_per_dev"] = p1["flops"] + scale * (p2["flops"] - p1["flops"])
        rec["bytes_per_dev"] = p1["bytes"] + scale * (p2["bytes"] - p1["bytes"])
        rec["coll_bytes_per_dev"] = p1["coll"] + scale * (p2["coll"] - p1["coll"])
        by1 = p1["coll_detail"]["by_op"]
        by2 = p2["coll_detail"]["by_op"]
        rec["collectives"] = {
            "by_op": {
                op: int(by1.get(op, 0) + scale * (by2.get(op, 0) - by1.get(op, 0)))
                for op in set(by1) | set(by2)
            },
            "count_probe_d2": p2["coll_detail"]["count"],
        }
      except Exception as e:  # pragma: no cover
        rec["probe_error"] = str(e)[-2000:]
        rec["flops_per_dev"] = scanned_costs["flops"]
        rec["bytes_per_dev"] = scanned_costs["bytes"]
        rec["coll_bytes_per_dev"] = scanned_costs["coll"]
        rec["collectives"] = scanned_costs["coll_detail"]

    tokens = shape.global_batch * (shape.seq_len if kind != "decode" else 1)
    n_active = active_param_count(cfg, build_model(cfg).param_specs())
    rec["model_flops_global"] = model_flops(n_params, n_active, tokens, kind)
    rec["hlo_flops_global"] = rec["flops_per_dev"] * chips
    rec["useful_flops_ratio"] = (
        rec["model_flops_global"] / rec["hlo_flops_global"]
        if rec["hlo_flops_global"] else 0.0
    )
    rec.update(
        roofline_terms(
            device_kind=TARGET_DEVICE_KIND,
            flops_per_dev=rec["flops_per_dev"],
            bytes_per_dev=rec["bytes_per_dev"],
            coll_bytes_per_dev=rec["coll_bytes_per_dev"],
        )
    )
    rec["wall_s"] = round(time.time() - t_start, 2)

    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        suffix = f"_{tag}" if tag else ""
        path = os.path.join(out_dir, f"{arch}_{shape_name}_{mesh_kind}{suffix}.json")
        with open(path, "w") as f:
            json.dump(rec, f, indent=1)
    return rec


def _driver(mesh_kinds, out_dir: str, archs=None, shapes=None) -> int:
    """Run every cell in a fresh subprocess (isolates compile memory)."""
    from repro.configs import all_cells

    cells = all_cells()
    if archs:
        cells = [c for c in cells if c[0] in archs]
    if shapes:
        cells = [c for c in cells if c[1] in shapes]
    failures = []
    for mesh_kind in mesh_kinds:
        for arch, shape in cells:
            suffix = os.path.join(out_dir, f"{arch}_{shape}_{mesh_kind}.json")
            if os.path.exists(suffix):
                print(f"[dryrun] skip cached {arch} x {shape} x {mesh_kind}")
                continue
            cmd = [
                sys.executable, "-m", "repro.launch.dryrun",
                "--arch", arch, "--shape", shape, "--mesh", mesh_kind,
                "--out", out_dir,
            ]
            if mesh_kind == "multi":
                cmd.append("--no-probes")  # roofline table is single-pod only
            print(f"[dryrun] {arch} x {shape} x {mesh_kind} ...", flush=True)
            try:
                r = subprocess.run(cmd, capture_output=True, text=True, timeout=2400)
            except subprocess.TimeoutExpired as te:
                class _R:  # noqa
                    returncode = 1
                    stdout = (te.stdout or b"").decode() if isinstance(te.stdout, bytes) else (te.stdout or "")
                    stderr = "TIMEOUT after 2400s"
                r = _R()
            if r.returncode != 0:
                failures.append((arch, shape, mesh_kind))
                err_path = os.path.join(out_dir, f"{arch}_{shape}_{mesh_kind}.err")
                os.makedirs(out_dir, exist_ok=True)
                with open(err_path, "w") as f:
                    f.write(r.stdout[-4000:] + "\n" + r.stderr[-8000:])
                print(f"[dryrun]   FAILED (see {err_path})")
            else:
                print(r.stdout.strip().splitlines()[-1] if r.stdout.strip() else "ok")
    print(f"[dryrun] done; {len(failures)} failures: {failures}")
    return 1 if failures else 0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--out", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--rules", default=None, help="JSON logical-rule overrides")
    ap.add_argument("--tag", default="", help="suffix for perf-iteration records")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--no-probes", action="store_true")
    ap.add_argument("--moments-dtype", default="float32")
    ap.add_argument("--cfg", default=None, help="JSON ModelConfig field overrides")
    args = ap.parse_args()

    mesh_kinds = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    if args.all:
        return _driver(mesh_kinds, args.out or "experiments/dryrun",
                       archs=args.arch.split(",") if args.arch else None,
                       shapes=args.shape.split(",") if args.shape else None)

    overrides = json.loads(args.rules) if args.rules else None
    for mk in mesh_kinds:
        try:
            rec = _run_cell(args.arch, args.shape, mk, args.out, overrides, args.tag,
                            microbatches=args.microbatches,
                            probes=not args.no_probes,
                            moments_dtype=args.moments_dtype,
                            cfg_overrides=json.loads(args.cfg) if args.cfg else None)
            print(json.dumps(
                {k: rec[k] for k in (
                    "arch", "shape", "mesh", "chips", "flops_per_dev",
                    "bytes_per_dev", "coll_bytes_per_dev", "t_compute_s",
                    "t_memory_s", "t_collective_s", "dominant",
                    "peak_bytes_per_dev", "useful_flops_ratio", "compile_s",
                ) if k in rec}
            ))
        except Exception:
            traceback.print_exc()
            return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
