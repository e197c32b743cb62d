"""One traced run of a cell, read through the engine's own spans.

    python3 bench/program_trace.py --workload granite-8b-d8.offline \
        --seed 7 --seconds 15 --out <dir>

Runs the cell as ``bench/run.py --trace 1`` does, keeps the profiler
trace under ``--out``, and prints one JSON object with:

* ``line``: the run's own result line;
* ``idle_gaps``: the longest device-idle gaps of the window, each named
  by the innermost span among the harness's ``bench.*`` and the engine's
  ``serve.*`` spans that the profiler recorded (``host.other`` if none);
* ``tick_scopes``: device seconds of the decode tick's leaf operations,
  grouped by the named scope (``attention``, ``ffn``, ``unembed``,
  ``sample``) that the compiled tick's HLO text gives each operation
  (``other`` for the rest, ``unmapped`` for names the text lacks);
* ``from_profiler``: the per-layer metrics that time the engine's spans,
  computed from the profiler's copy of them, beside the values in
  ``line`` that the run computed from the tracer's ring buffer;
* ``window_ticks_per_s``: engine ticks per second in the traced window
  (the untraced run's ``window_ticks`` / ``--seconds`` is its match).
"""

from __future__ import annotations

import bisect
import json
import re
import sys
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

sys.path[:0] = [str(Path(__file__).resolve().parents[1]),
                str(Path(__file__).resolve().parents[1] / "src")]

from bench import program_spans, trace_reduce  # noqa: E402
from bench.trace_reduce import Event  # noqa: E402

SCOPES = ("attention", "ffn", "unembed", "sample")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]+)"')
_SCOPE = re.compile(r"(?:^|/)(?:[\w.]+\()?(" + "|".join(SCOPES) + r")\)?(?:/|$)")


def op_scopes(hlo_text: str) -> Dict[str, str]:
    """Instruction name (no ``%``) -> the named scope in its ``op_name``
    metadata, ``other`` where it names none of ``SCOPES`` or has none (the
    compiler drops it from, e.g., the weight casts it hoists)."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m:
            op = _OP_NAME.search(line)
            s = _SCOPE.search(op.group(1)) if op else None
            out[m.group(1)] = s.group(1) if s else "other"
    return out


def tick_scopes(ops: Sequence[Event], modules: Sequence[Event],
                scopes: Dict[str, str], tick: str = "jit_tick") -> Dict[str, float]:
    """Device seconds of the leaf operations that ran inside ``tick``'s
    executions, by scope."""
    runs = sorted((a, b) for name, a, b in modules if name.startswith(tick))
    starts = [a for a, _ in runs]
    acc: Dict[str, float] = {}
    for name, a, b in trace_reduce.leaves(ops):
        i = bisect.bisect_right(starts, a) - 1
        if i < 0 or b > runs[i][1]:
            continue
        scope = scopes.get(name.lstrip("%"), "unmapped")
        acc[scope] = acc.get(scope, 0.0) + (b - a)
    return acc


def host_spans(path: str) -> Tuple[List[Event], List[Event]]:
    """The host planes' ``bench.*`` and ``serve.*`` events of an xplane."""
    from jax.profiler import ProfileData

    harness: List[Event] = []
    engine: List[Event] = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                dest = (harness if e.name.startswith("bench.")
                        else engine if e.name.startswith("serve.") else None)
                if dest is not None:
                    t0 = e.start_ns * 1e-9
                    dest.append((e.name, t0, t0 + e.duration_ns * 1e-9))
    harness.sort(key=lambda e: e[1])
    engine.sort(key=lambda e: e[1])
    return harness, engine


def from_spans(busy, engine: Sequence[Event]) -> Dict[str, Optional[float]]:
    """The per-layer metrics that time the engine's spans, from ``engine``."""
    return {
        "prefill.idle_in_chunk_share": program_spans.idle_in_chunk_share(busy, engine),
        "sched.idle_outside_prefill_ms_per_tick":
            program_spans.idle_outside_prefill_ms_per_tick(busy, engine),
    }


def tick_hlo(eng) -> str:
    """The compiled decode tick's HLO text, for the engine's own shapes."""
    import jax.numpy as jnp
    import numpy as np

    n = eng.cb.num_slots
    sampling = (jnp.zeros(n, jnp.int32),) * 2 if eng.cb.temperature > 0 else (None, None)
    lowered = eng._tick.lower(eng.params, eng.pool, jnp.asarray(np.zeros((n, 1), np.int32)),
                              eng._tables_dev, *sampling)
    return lowered.compile().as_text()


def main(argv: Optional[Sequence[str]] = None, opts=None) -> int:
    """``opts``: a ``run.Options`` (tests run a small cell on the CPU)."""
    import argparse
    import dataclasses

    from bench import run

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=run.TRACE_SECONDS)
    ap.add_argument("--out", required=True)
    a = ap.parse_args(argv)
    args = run.parse_args(["--workload", a.workload, "--seed", str(a.seed),
                           "--seconds", str(a.seconds), "--trace", "1",
                           "--keep-trace", a.out])
    hlo: Dict[str, str] = {}
    out = run.run_cell(args, dataclasses.replace(
        opts or run.Options(),
        on_engine=lambda eng: hlo.setdefault("tick", tick_hlo(eng))))
    if out is None:
        return 2
    line = out["line"]
    path = sorted(Path(a.out).glob("*.xplane.pb"))[-1]
    tr = trace_reduce.load(str(path))
    harness, engine = host_spans(str(path))
    busy = trace_reduce.union(tr.ops)
    lo, hi = harness[0][1], harness[-1][2]
    scopes = op_scopes(hlo["tick"])
    (Path(a.out) / "tick.hlo.txt").write_text(hlo["tick"])
    result = {
        "line": line,
        "idle_gaps": trace_reduce.idle_gaps(busy, harness + engine, lo, hi),
        "tick_scopes": tick_scopes(tr.ops, tr.modules, scopes),
        "from_profiler": from_spans(busy, engine),
        "window_ticks_per_s": (out["info"]["window_ticks"]
                               / min(a.seconds, run.TRACE_SECONDS)),
        "engine_spans": {n: sum(1 for s in engine if s[0] == n)
                         for n in sorted({s[0] for s in engine})},
        "info": out["info"],
    }
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
