"""Open loop: independent users, Poisson arrivals at ``rate_per_s`` in
wall time.  The schedule starts ``lead_in_s`` before the measured window,
so the window opens on a loaded server, and a request is due at its
scheduled time whether or not the server kept up."""

from __future__ import annotations

import math

import numpy as np


def count(mix, seconds: float) -> int:
    return int(math.ceil(mix["rate_per_s"] * (mix["lead_in_s"] + seconds))) + 1


def gaps(mix, n: int, rng: np.random.Generator) -> np.ndarray:
    g = rng.exponential(1.0, n)
    return g * n / (mix["rate_per_s"] * g.sum())  # mean gap exactly 1/rate


def feed(client, now: float) -> None:
    """Submit every request whose due time has passed, timed from it."""
    reqs = client.requests
    while client.next < len(reqs) and client.origin + reqs[client.next].due_s <= now:
        client.submit(reqs[client.next], client.origin + reqs[client.next].due_s)


def lead_in(client, mix) -> None:
    client.run(client.origin + mix["lead_in_s"])
