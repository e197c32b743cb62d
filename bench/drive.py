"""The client side of a run: submit on schedule, tick the engine, and
record when each token reached the host.

Host spans for the profiler come from here alone (``bench.submit``,
``bench.step``, ``bench.wait_arrival``), so a traced run can say what
the host was doing in each gap of the device's timeline.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List, Optional

from jax.profiler import TraceAnnotation


@dataclasses.dataclass
class Served:
    """One request as its client saw it."""

    index: int
    prompt_len: int
    max_new: int
    due: float  # when it was due to be sent (host clock)
    uid: int = -1
    times: List[float] = dataclasses.field(default_factory=list)
    tokens: List[int] = dataclasses.field(default_factory=list)

    @property
    def finished(self) -> bool:
        return len(self.tokens) >= self.max_new


@dataclasses.dataclass
class Step:
    t0: float
    t1: float
    decode_slots: int  # slots that produced a decode token this tick
    live_rows: int  # KV rows those slots attended over, summed


class Client:
    """Feeds one engine from a list of generated requests.

    Before every tick the traffic's kind (``bench/kinds``) submits what is
    due; with nothing to do, the client sleeps until the next arrival.
    """

    def __init__(self, eng, requests, kind, num_slots: int,
                 clock: Callable[[], float] = time.perf_counter):
        self.eng = eng
        self.kind = kind
        self.num_slots = num_slots
        self.clock = clock
        self.requests = requests
        self.next = 0
        self.origin: Optional[float] = None
        self.served: Dict[int, Served] = {}  # by uid
        self.by_index: List[Served] = []
        self.steps: List[Step] = []

    def start(self, origin: float) -> None:
        """Anchor the arrival schedule at host time ``origin``."""
        self.origin = origin

    def submit(self, req, due: float) -> None:
        with TraceAnnotation("bench.submit"):
            uid = self.eng.submit(req.prompt, req.max_new_tokens)
        rec = Served(req.index, len(req.prompt), req.max_new_tokens, due, uid)
        self.served[uid] = rec
        self.by_index.append(rec)
        self.next += 1

    def step(self) -> Step:
        with TraceAnnotation("bench.step"):
            t0 = self.clock()
            events = self.eng.step()
            t1 = self.clock()
        decode = rows = 0
        for ev in events:
            rec = self.served[ev.uid]
            rec.times.append(t1)
            rec.tokens.append(int(ev.token))
            if ev.index >= 1:  # a decode token: row prompt + index - 1 written
                decode += 1
                rows += rec.prompt_len + ev.index
        st = Step(t0, t1, decode, rows)
        self.steps.append(st)
        return st

    def run(self, until: float,
            stop: Optional[Callable[[Step], bool]] = None) -> None:
        """Serve until host time ``until`` (a tick that starts before it
        runs to its end), or until ``stop(step)`` says so."""
        while True:
            now = self.clock()
            if now >= until:
                return
            self.kind.feed(self, now)
            if self.eng.scheduler.done():
                if self.next >= len(self.requests):
                    return
                wake = min(until, self.origin + self.requests[self.next].due_s)
                with TraceAnnotation("bench.wait_arrival"):
                    time.sleep(max(0.0, wake - self.clock()))
                continue
            st = self.step()
            if stop is not None and stop(st):
                return
