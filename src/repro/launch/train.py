"""Training launcher.

  PYTHONPATH=src python -m repro.launch.train --arch granite_8b --smoke \\
      --steps 50 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt

On real TPU fleets: one process per host, jax.distributed.initialize()
first (flag --multihost), then the same code path — the mesh spans all
pods.  XLA latency-hiding flags for collective/compute overlap are set
here (no-ops on CPU).
"""

from __future__ import annotations

import argparse
import os
import sys

TPU_PERF_FLAGS = (
    "--xla_tpu_enable_async_collective_fusion=true "
    "--xla_tpu_enable_async_collective_fusion_fuse_all_gather=true "
    "--xla_tpu_overlap_compute_collective_tc=true "
    "--xla_enable_async_all_gather=true "
)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true", help="reduced config")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--mesh", default=None, help="e.g. '4,2' => (data,model)")
    ap.add_argument("--multihost", action="store_true")
    args = ap.parse_args()

    if args.multihost:  # pragma: no cover - needs a real fleet
        os.environ.setdefault("XLA_FLAGS", TPU_PERF_FLAGS)
        import jax

        jax.distributed.initialize()
    import jax

    from repro.configs import get_config, get_smoke_config
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import make_mesh
    from repro.train.loop import LoopConfig, run_train
    from repro.train.step import TrainConfig

    enable_compile_cache()
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    mesh = None
    if args.mesh:
        shape = tuple(int(x) for x in args.mesh.split(","))
        axes = ("data", "model")[: len(shape)] if len(shape) <= 2 else ("pod", "data", "model")
        mesh = make_mesh(shape, axes)

    res = run_train(
        cfg,
        TrainConfig(peak_lr=args.lr, total_steps=args.steps, warmup_steps=max(1, args.steps // 10), microbatches=args.microbatches),
        LoopConfig(
            num_steps=args.steps, batch=args.batch, seq_len=args.seq,
            ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
        ),
        mesh=mesh,
    )
    print(f"final loss: {res['history'][-1]['loss']:.4f} after {res['final_step']} steps")
    return 0


if __name__ == "__main__":
    sys.exit(main())
