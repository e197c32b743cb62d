"""Readings the limit of ``correct`` is set from, on the chip, at a
cell's own size and load, several seeds in one process:

    python3 bench/control.py --workload granite-8b-d8.offline \
        --seeds 11 12 13 --seconds 20

For each seed it serves a window as a benchmark run does, then puts the
plain reference run in float8 (e4m3), the step below the bfloat16 the
configurations state, in the system's place and judges it as a run is
judged: ``correct`` must come out false.  Over the same sample of
finished requests it prints the widest gap of the system (the lower
reading) and of the control (the upper reading).  The benchmark's own
runs never run the control.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[1]),
                str(Path(__file__).resolve().parents[1] / "src")]

from bench import run  # noqa: E402

CONTROL = "float8_e4m3fn"  # the step below bfloat16 compute


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)
    for seed in args.seeds:
        out = run.run_cell(run.parse_args(
            ["--workload", args.workload, "--seed", str(seed),
             "--seconds", str(args.seconds), "--trace", "0"]),
            run.Options(control=CONTROL))
        if out is None:
            return 2
        info, line = out["info"], out["line"]
        print(json.dumps({
            "workload": args.workload, "seed": seed,
            "control_correct": line["correct"],
            "system_gap": max(info["system_gaps"]),
            "control_gap": line["checks"]["token_gap"]["value"],
            "limit": line["checks"]["token_gap"]["limit"],
            "system_gaps": info["system_gaps"],
            "control_gaps": info["control_gaps"],
            "sample_tokens": line["checks"]["sampled_tokens"]["value"],
            "window_compiles": line["checks"]["window_compiles"]["value"],
            "e2e": info["e2e"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
