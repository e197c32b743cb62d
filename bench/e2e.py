"""End-to-end numbers of one measured window ``[t0, t1)``, on the host
clock, from the client-side records of ``drive.Served``.

* time to first token: every request due in the window, from its
  scheduled arrival; one with no first token by ``t1`` counts with the
  time it has waited so far (censored, never dropped);
* gaps between tokens: every pair of consecutive tokens of a request that
  both reached the host inside the window;
* output tokens per second: every token that reached the host inside the
  window, over the window's length.

Percentiles are over all values, with numpy's default linear
interpolation.
"""

from __future__ import annotations

from typing import Iterable, List, Tuple

import numpy as np


def ttft(served: Iterable, t0: float, t1: float) -> Tuple[List[float], int]:
    """Times to first token of the requests due in the window, and how
    many of them are censored at ``t1``."""
    out, censored = [], 0
    for r in served:
        if not t0 <= r.due < t1:
            continue
        if r.times and r.times[0] <= t1:
            out.append(r.times[0] - r.due)
        else:
            out.append(t1 - r.due)
            censored += 1
    return out, censored


def itl(served: Iterable, t0: float, t1: float) -> List[float]:
    out = []
    for r in served:
        ts = [t for t in r.times if t0 <= t <= t1]
        out.extend(np.diff(ts).tolist())
    return out


def output_tokens(served: Iterable, t0: float, t1: float) -> int:
    return sum(1 for r in served for t in r.times if t0 <= t <= t1)


def p95(values: List[float]) -> float:
    return float(np.percentile(values, 95)) if values else float("nan")
