"""The engine's own spans (``serve.*``) on the device trace's clock.

A traced run keeps the program's tracer recording: its ring buffer holds
one ``serve.step`` span per engine tick and the tick's phases nested in
it (``serve.admit``, ``serve.prefill_chunk``, ``serve.prefill_finish``,
``serve.blocks``, ``serve.decode``), stamped on the host's
``perf_counter``, and a ``serve.compile`` instant per lowering and
backend compile, with the engine phase it fell in.  The harness's
``bench.step`` annotation wraps each ``engine.step()`` call in the
profiler's trace, so the n-th ``serve.step`` of the window is the n-th
``bench.step``: each tick's spans move onto the trace's clock by the
offset between the two ends of that pair (the harness reads one clock
between the engine's return and the annotation's end; its start holds
more, the first call of a tick's annotations among it).

A program whose engine records no ``serve.step`` gives nothing here, and
the readers built on it read None.
"""

from __future__ import annotations

import bisect
from typing import Any, List, Optional, Sequence

from bench import trace_reduce
from bench.trace_reduce import Event

STEP = "serve.step"
PREFILL = ("serve.prefill_chunk", "serve.prefill_finish")


def ring() -> List[Any]:
    """The program tracer's events (``repro.obs`` TraceEvent rows): in a
    traced run, everything recorded since the window opened."""
    from repro import obs

    return list(obs.get_tracer().events)


def aligned(trace_spans: Sequence[Event], events: Sequence[Any]) -> List[Event]:
    """The ring buffer's ``serve.*`` spans, as ``(name, start, end)`` in
    seconds on the trace's clock, paired tick by tick with the trace's
    ``bench.step`` spans; spans outside every tick are left out."""
    steps = sorted((e for e in events if e.ph == "X" and e.name == STEP),
                   key=lambda e: e.ts)
    host = [s for s in trace_spans if s[0] == "bench.step"]
    # the window ends on a tick in both: pair from the end, so a ring that
    # lost its oldest events still lines up
    n = min(len(steps), len(host))
    pairs = list(zip(host[len(host) - n:], steps[len(steps) - n:]))
    starts = [r.ts for _, r in pairs]
    out: List[Event] = []
    for e in events:
        if e.ph != "X" or not e.name.startswith("serve."):
            continue
        i = bisect.bisect_right(starts, e.ts) - 1
        if i < 0:
            continue
        (_, _, b), r = pairs[i]
        if e.ts + e.dur > r.ts + r.dur + 1e-3:  # not inside this tick
            continue
        shift = b - 1e-6 * (r.ts + r.dur)
        out.append((e.name, 1e-6 * e.ts + shift,
                    1e-6 * (e.ts + e.dur) + shift))
    out.sort(key=lambda s: s[1])
    return out


def of(ctx) -> List[Event]:
    """``aligned`` for a reader's context: its trace's host spans against
    the program tracer's ring buffer."""
    if ctx.trace is None:
        return []
    return aligned(ctx.trace.spans, ring())


def idle_s(busy: Sequence[trace_reduce.Interval], spans: Sequence[Event]) -> float:
    """Seconds of ``spans`` in which the device ran nothing."""
    return sum((b - a) - trace_reduce.covered(busy, a, b) for _, a, b in spans)


def idle_in_chunk_share(busy, spans: Sequence[Event]) -> Optional[float]:
    """% of the ``serve.prefill_chunk`` spans' time the device was idle."""
    chunks = [s for s in spans if s[0] == "serve.prefill_chunk"]
    share = trace_reduce.idle_share(busy, chunks)
    return None if share is None else 100.0 * share


def idle_outside_prefill_ms_per_tick(busy, spans: Sequence[Event]) -> Optional[float]:
    """Device-idle ms per ``serve.step`` outside its prefill spans."""
    steps = [s for s in spans if s[0] == STEP]
    if not steps:
        return None
    prefill = [s for s in spans if s[0] in PREFILL]
    return 1e3 * (idle_s(busy, steps) - idle_s(busy, prefill)) / len(steps)


def lowerings_per_chunk(events: Sequence[Any]):
    """Programs lowered inside the prefill chunks per chunk call, from the
    ``serve.compile`` instants and the ``serve.prefill_chunk`` spans; None
    where the program records no ticks or ran no chunk."""
    if not any(e.ph == "X" and e.name == STEP for e in events):
        return None
    chunks = sum(1 for e in events
                 if e.ph == "X" and e.name == "serve.prefill_chunk")
    if chunks == 0:
        return None
    lowered = sum(1 for e in events if e.name == "serve.compile"
                  and e.args.get("stage") == "lower"
                  and e.args.get("phase") == "prefill_chunk")
    return lowered / chunks
