"""From a profiler trace (``.xplane.pb``) to the intervals the per-layer
metrics and the ``breakdown`` are computed from.

``load`` keeps three lists, all in seconds on the trace's own clock:

* ``ops``     - every operation that ran on the first TPU device (its
  "XLA Ops" line), under the name the trace gives it, cut before its
  HLO text (``%fusion.148``, ``%paged_flash_attention.12``);
* ``modules`` - every program execution on that device ("XLA Modules"),
  e.g. ``jit_tick(...)``;
* ``spans``   - the benchmark's host annotations (names ``bench.*``).

The rest are plain interval arithmetic, checked in ``bench/tests``
against a trace recorded on a v5e and committed in ``bench/testdata``.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Callable, Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]
Event = Tuple[str, float, float]  # name, start, end


@dataclasses.dataclass
class Trace:
    ops: List[Event]
    modules: List[Event]
    spans: List[Event]


def _device_plane(planes) -> Optional[object]:
    best = None
    for plane in planes:
        m = re.match(r"^/device:TPU:(\d+)$", plane.name)
        if m and (best is None or int(m.group(1)) < best[0]):
            best = (int(m.group(1)), plane)
    return best[1] if best else None


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = list(data.planes)
    ops: List[Event] = []
    modules: List[Event] = []
    spans: List[Event] = []
    dev = _device_plane(planes)
    if dev is not None:
        for line in dev.lines:
            dest = {"XLA Ops": ops, "XLA Modules": modules}.get(line.name)
            if dest is None:
                continue
            for e in line.events:
                t0 = e.start_ns * 1e-9
                dest.append((e.name.split(" = ", 1)[0], t0,
                             t0 + e.duration_ns * 1e-9))
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("bench."):
                    t0 = e.start_ns * 1e-9
                    spans.append((e.name, t0, t0 + e.duration_ns * 1e-9))
    for lst in (ops, modules, spans):
        lst.sort(key=lambda e: e[1])
    return Trace(ops, modules, spans)


def union(events: Sequence[Event]) -> List[Interval]:
    """Merged busy intervals of ``events``."""
    out: List[List[float]] = []
    for _, a, b in sorted(events, key=lambda e: e[1]):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def covered(busy: Sequence[Interval], a: float, b: float) -> float:
    """Seconds of ``[a, b]`` that the merged intervals ``busy`` cover."""
    return sum(max(0.0, min(b, y) - max(a, x)) for x, y in busy)


def total(events: Sequence[Event],
          keep: Callable[[str], bool] = lambda name: True) -> Tuple[float, int]:
    """Summed duration and count of the events whose name ``keep``s."""
    picked = [b - a for name, a, b in events if keep(name)]
    return sum(picked), len(picked)


def between(events: Sequence[Event], a: float, b: float) -> List[Event]:
    return [e for e in events if e[1] >= a and e[2] <= b]


def idle_share(busy: Sequence[Interval], spans: Sequence[Event]) -> Optional[float]:
    """1 - device-busy time / host span time, over ``spans``."""
    span_s = sum(b - a for _, a, b in spans)
    if span_s <= 0:
        return None
    return 1.0 - sum(covered(busy, a, b) for _, a, b in spans) / span_s


def leaves(ops: Sequence[Event]) -> List[Event]:
    """The operations that hold no other: a loop or call op (``%while``)
    spans the ops it runs, which the trace lists after it."""
    ev = sorted(ops, key=lambda e: (e[1], -e[2]))
    return [e for e, nxt in zip(ev, ev[1:] + [None])
            if nxt is None or not (e[1] <= nxt[1] and nxt[2] <= e[2])]


def top_ops(ops: Sequence[Event], n: int = 10) -> List[List]:
    """The ``n`` leaf operation names with the most device time: [name, s]."""
    acc: Dict[str, float] = {}
    for name, a, b in leaves(ops):
        acc[name] = acc.get(name, 0.0) + (b - a)
    return [[k, v] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(busy: Sequence[Interval], spans: Sequence[Event],
              a: float, b: float, n: int = 10) -> List[List]:
    """The ``n`` longest device-idle gaps inside ``[a, b]``, each named by
    the host span its midpoint fell in (the innermost, i.e. the shortest,
    when several do), ``host.other`` when none: [name, s]."""
    gaps = []
    edge = a
    for x, y in list(busy) + [(b, b)]:
        lo, hi = max(edge, a), min(x, b)
        if hi > lo:
            gaps.append((lo, hi))
        edge = max(edge, y)
    out = []
    for lo, hi in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        mid = 0.5 * (lo + hi)
        inside = [s for s in spans if s[1] <= mid <= s[2]]
        name = min(inside, key=lambda s: s[2] - s[1])[0] if inside else "host.other"
        out.append([name, hi - lo])
    return out
