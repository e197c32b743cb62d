"""Warm every program a cell's traffic will run, and only those.

Each chunk of a prompt calls the model's ``prefill`` (first chunk) or
``prefill_extend`` (later chunks), each jitted once per chunk shape: a
program is keyed by the chunk length, the staging cache's rows and, for
MoE, the expert capacity of the whole prompt.  The direct calls here
reach the same jit cache entries as the engine's own.  ``prefill_shapes`` lists
every such key the mix can reach; ``warm_prefill`` runs each once.
``warm_engine`` then serves one short request per staging width through
the engine itself, which compiles its decode tick, pool write, table push
and slot reset.
"""

from __future__ import annotations

from typing import Dict, List, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def _bucket_rows(rows: int, block: int, slot_blocks: int) -> int:
    """Staging rows of a ``rows``-row prompt: its block count rounded up
    to a power of two, clamped to a slot's table."""
    blocks = -(-rows // block)
    b = 1
    while b < blocks:
        b *= 2
    return min(b, slot_blocks) * block


def prompt_lengths(mix: Dict) -> List[int]:
    p = mix["prompt_tokens"]
    return list(range(p["min"], p["max"] + 1))


def prefill_shapes(eng, mix: Dict) -> Set[Tuple[str, int, int, object]]:
    """``(kind, chunk, staging rows, capacity)`` of every prefill program
    the mix can reach: the engine splits a prompt's share of the tick's
    budget into power-of-two chunks, so chunks are the powers of two up
    to the budget."""
    budget = eng.cb.prefill_chunk_tokens
    block = eng.block_pool.block_size
    slot_blocks = -(-eng.cb.max_len // block)
    out = set()
    for rows in prompt_lengths(mix):
        ts = _bucket_rows(rows, block, slot_blocks)
        cap = eng.model.moe_prefill_capacity(rows)
        c = 1
        while c <= min(budget, rows):
            out.add(("prefill", c, ts, cap))
            if c < rows:
                out.add(("extend", c, ts, cap))
            c *= 2
    return out


def warm_prefill(eng, shapes) -> int:
    """Run every prefill program in ``shapes`` once, as the engine calls
    it; returns how many were run."""
    first: Dict[Tuple[int, object], object] = {}
    tok = np.zeros(max(c for _, c, _, _ in shapes), np.int32)
    for kind, c, ts, cap in sorted(shapes, key=lambda s: (s[0] != "prefill", s[1:3])):
        chunk = jnp.asarray(tok[:c])[None]
        if kind == "prefill":
            logits, cache = eng.model.prefill(
                eng.params, chunk, eng.cb.max_len, cache_t=ts, moe_capacity=cap)
            first.setdefault((ts, cap), cache)
        else:
            base = first.get((ts, cap))
            if base is None:
                _, base = eng.model.prefill(
                    eng.params, jnp.asarray(tok[:1])[None], eng.cb.max_len,
                    cache_t=ts, moe_capacity=cap)
                first[(ts, cap)] = base
            logits, _ = eng.model.prefill_extend(eng.params, base, chunk,
                                                 moe_capacity=cap)
        jax.block_until_ready(logits)
    return len(shapes)


def warm_engine(eng, mix: Dict, vocab: int, seed: int) -> int:
    """Serve one three-token request per staging width the mix reaches,
    through ``submit`` and ``step``; returns the ticks it took."""
    block = eng.block_pool.block_size
    slot_blocks = -(-eng.cb.max_len // block)
    widths = {}
    for rows in prompt_lengths(mix):
        widths.setdefault(_bucket_rows(rows, block, slot_blocks), rows)
    rng = np.random.default_rng(seed)
    for rows in widths.values():
        eng.submit(rng.integers(0, vocab, rows, dtype=np.int32), 3)
    ticks = 0
    while not eng.scheduler.done():
        eng.step()
        ticks += 1
    return ticks
