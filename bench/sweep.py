"""Find an open-loop cell's knee on the chip: serve one window at each of
a few fixed rates, in one process, and print what each did.

    python3 bench/sweep.py --workload granite-8b-d8.chat \
        --rates 2 3 4 5 --seconds 30 --seed 5

The knee is the highest rate at which the pending queue does not grow over
the window and every request due in its first half finished by its close.
The cell's traffic file then fixes its rate at about four fifths of it.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[1]),
                str(Path(__file__).resolve().parents[1] / "src")]

from bench import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=5)
    args = ap.parse_args(argv)
    for rate in args.rates:
        out = run.run_cell(run.parse_args(
            ["--workload", args.workload, "--seed", str(args.seed),
             "--seconds", str(args.seconds), "--trace", "0",
             "--rate", str(rate)]), run.Options())
        if out is None:
            return 2
        info = out["info"]
        sustained = (info["pending_at_close"] <= max(info["pending_at_open"], 1)
                     and info["first_half_finished"] == 1.0)
        print(json.dumps({"rate_per_s": rate, "sustained": sustained,
                          "correct": out["line"]["correct"], **info}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
