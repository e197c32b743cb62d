"""Backlog: an offline batch job.  ``requests`` requests, all due at once;
before every tick the engine's queue is topped up to its slot count, and
the window opens once every slot decodes."""

from __future__ import annotations

import numpy as np

FILL_S = 300.0  # a lead-in that has not filled the slot pool by now fails


def count(mix, seconds: float) -> int:
    return int(mix["requests"])


def gaps(mix, n: int, rng: np.random.Generator) -> np.ndarray:
    return np.zeros(n)


def feed(client, now: float) -> None:
    pending = client.eng.scheduler.pending
    while client.next < len(client.requests) and len(pending) < client.num_slots:
        client.submit(client.requests[client.next], client.clock())


def lead_in(client, mix) -> None:
    full = client.num_slots
    client.run(client.origin + FILL_S, stop=lambda st: st.decode_slots == full)
    if not client.steps or client.steps[-1].decode_slots != full:
        raise SystemExit("the backlog never filled the slot pool")
