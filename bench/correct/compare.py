"""What decides ``correct`` for a served model.

Once the window has closed, a sample of the requests it finished, drawn
from the seed and always holding the longest one, is run through the
plain reference once over each prompt with its served tokens.  For every
served token the gap by which the reference's logit for it lies below the
reference's best logit at that position is taken, in units of that row's
standard deviation; the number compared is the widest gap.  All requests
are greedy, so a served token that agrees with the reference reads 0.

The limit on the widest gap is the configuration's own
(``correct.token_gap_limit`` in its file), set from the system's readings
over a dozen seeds and the control's on the chip (PERF.md).

The control (``control_gap``) puts the reference in the system's place at
a lower precision: at each of the same positions it reads the gap of the
token that the lower precision ranks first.
"""

from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np

SAMPLE_TOKENS = 384  # served tokens the sample holds at least


def sample(finished: Dict[int, dict], seed: int,
           min_tokens: int = SAMPLE_TOKENS) -> List[int]:
    """Keys of the finished requests to check: the one with the most
    served tokens, then others in an order drawn from ``seed`` until the
    sample holds ``min_tokens`` served tokens (or every request)."""
    if not finished:
        return []
    keys = sorted(finished)
    longest = max(keys, key=lambda k: (len(finished[k]["tokens"]), -k))
    rest = [k for k in keys if k != longest]
    rest = [rest[i] for i in np.random.default_rng(seed).permutation(len(rest))]
    picked, total = [longest], len(finished[longest]["tokens"])
    for k in rest:
        if total >= min_tokens:
            break
        picked.append(k)
        total += len(finished[k]["tokens"])
    return picked


def _rows(logits: np.ndarray, prompt_len: int, n: int) -> np.ndarray:
    # row p - 1 + i predicts served token i
    return np.asarray(logits, np.float64)[prompt_len - 1: prompt_len - 1 + n]


def token_gaps(ref_logits: np.ndarray, prompt_len: int,
               served: Sequence[int]) -> np.ndarray:
    """Per served token: (reference best - reference logit of the served
    token) / that row's standard deviation."""
    rows = _rows(ref_logits, prompt_len, len(served))
    got = rows[np.arange(len(served)), np.asarray(served)]
    return (rows.max(-1) - got) / rows.std(-1)


def control_gaps(ref_logits: np.ndarray, low_logits: np.ndarray,
                 prompt_len: int, n: int) -> np.ndarray:
    """The same gap for the token the lower precision ranks first."""
    low = _rows(low_logits, prompt_len, n)
    return token_gaps(ref_logits, prompt_len, list(np.argmax(low, -1)))
