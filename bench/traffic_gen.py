"""The one traffic generator: every mix is a data file it reads.

Every run gets the same schedule: request sizes, their order and their
arrival times are drawn once from a fixed stream, and only the token ids
come from the run's seed.  So every seed does the same work at the same
moments, and the spread between runs is the system's, not the mix's.

A mix's ``kind`` names a module ``bench/kinds/<kind>.py`` that gives the
number of requests (``count``), their arrival gaps (``gaps``), how the
client submits them (``feed``) and how the window is opened
(``lead_in``); a new kind is a new file there.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List

import numpy as np

from bench import common

MIX_SEED = 20240517  # fixes the sizes, their order and the arrival gaps


@dataclasses.dataclass
class Request:
    index: int
    due_s: float  # scheduled arrival, seconds after the schedule starts
    prompt: np.ndarray  # int32 token ids
    max_new_tokens: int


def _sizes(spec: Dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` lognormal sizes around ``median``, clipped to [min, max]."""
    raw = spec["median"] * np.exp(spec["sigma"] * rng.standard_normal(n))
    return np.ceil(np.clip(raw, spec["min"], spec["max"])).astype(np.int64)


def generate(mix: Dict, *, seed: int, seconds: float,
             vocab_size: int) -> List[Request]:
    """The run's requests, sorted by due time."""
    kind = common.traffic_kind(mix["kind"])
    n = kind.count(mix, seconds)
    # one fixed stream per quantity, so a longer window only appends
    prompts = _sizes(mix["prompt_tokens"], n, np.random.default_rng([MIX_SEED, 0]))
    outputs = _sizes(mix["output_tokens"], n, np.random.default_rng([MIX_SEED, 1]))
    gaps = kind.gaps(mix, n, np.random.default_rng([MIX_SEED, 2]))
    due = np.cumsum(gaps) - gaps[0]  # first request due at the start
    rng = np.random.default_rng(seed)
    return [
        Request(i, float(due[i]),
                rng.integers(0, vocab_size, int(prompts[i]), dtype=np.int32),
                int(outputs[i]))
        for i in range(n)
    ]
