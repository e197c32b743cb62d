"""Retrace and transfer regression tests (DESIGN.md §11).

The device-resident engine tick makes two quantitative promises:

* **Bounded retraces** — admission shapes are bucketed to powers of two
  (``serve.paged.bucket_blocks``), so a mixed-length paged workload
  compiles O(log W) admission-write variants, not one per block count;
  and a *repeated* workload compiles nothing at all.
* **Bounded transfers** — a steady tick performs one D2H transfer (the
  ``[S]`` sampled-token vector) and uploads no block-table bytes unless
  the allocator dirtied a row; the ``serve.bytes.h2d`` / ``serve.bytes.d2h``
  counters surface both.

Counters are observables of the engine's *own* jitted callables
(``jit_cache_entries``) — fresh engines own fresh jit caches, so the
repeat-workload assertion reuses one engine instance.
"""

import numpy as np
import pytest

import jax

from repro.configs import get_smoke_config
from repro.models.param import materialize
from repro.models.registry import build_model
from repro.serve.engine import ContinuousBatchingEngine, ContinuousConfig
from repro.serve.paged import bucket_blocks

KEY = jax.random.PRNGKey(0)
RNG = np.random.default_rng(3)
MAX_LEN = 40
SLOTS = 3


def test_bucket_blocks_is_pow2_and_clamped():
    assert [bucket_blocks(n, 10) for n in range(1, 11)] == [
        1, 2, 4, 4, 8, 8, 8, 8, 10, 10]
    assert bucket_blocks(0, 10) == 1
    assert bucket_blocks(99, 10) == 10
    assert bucket_blocks(3, 2) == 2  # cap below the bucket


def _engine(cfg, params, **kw):
    return ContinuousBatchingEngine(
        cfg, params,
        ContinuousConfig(num_slots=SLOTS, max_len=MAX_LEN,
                         kv_layout="paged", kv_block_size=4, **kw))


def _mixed_workload(cfg, n=20):
    """n mixed-length requests spanning many distinct block counts."""
    lens = [int(x) for x in RNG.integers(2, 33, size=n)]
    prompts = [RNG.integers(0, cfg.vocab_size, (n_,)).astype(np.int32)
               for n_ in lens]
    gens = [int(g) for g in RNG.integers(2, 6, size=n)]
    return prompts, gens


@pytest.fixture(scope="module")
def model():
    cfg = get_smoke_config("granite_8b")
    m = build_model(cfg)
    return cfg, materialize(m.param_specs(), KEY)


def test_mixed_lengths_compile_olog_admission_variants(model):
    cfg, params = model
    eng = _engine(cfg, params)
    prompts, gens = _mixed_workload(cfg)
    raw_blocks = {eng.block_pool.blocks_for_tokens(len(p)) for p in prompts}
    assert len(raw_blocks) >= 6  # the workload really is mixed-length
    for p, g in zip(prompts, gens):
        eng.submit(p, g)
    eng.run()
    # power-of-two bucketing: variants ~ log2(W), not one per block count
    w = eng._slot_blocks
    budget = int(np.ceil(np.log2(w))) + 2  # buckets 1,2,4,...,W
    variants = eng._write_slot_paged._cache_size()
    assert variants <= budget, (
        f"admission write compiled {variants} variants for "
        f"{len(raw_blocks)} distinct block counts (budget {budget})"
    )
    assert variants < len(raw_blocks)


def test_repeat_workload_zero_new_compilations_bounded_d2h(model):
    """Second identical 20-request run on the SAME engine: zero new jit
    entries across every engine-owned callable, and per-tick D2H stays at
    the single sampled-token vector (plus one token per admission)."""
    cfg, params = model
    eng = _engine(cfg, params)
    prompts, gens = _mixed_workload(cfg)

    def run_once():
        t0, a0 = eng.ticks, eng.metrics.counter("serve.requests.admitted").value()
        d0 = eng.metrics.counter("serve.bytes.d2h").value()
        for p, g in zip(prompts, gens):
            eng.submit(p, g)
        eng.run()
        return (eng.ticks - t0,
                eng.metrics.counter("serve.requests.admitted").value() - a0,
                eng.metrics.counter("serve.bytes.d2h").value() - d0)

    run_once()
    entries_after_first = eng.jit_cache_entries()
    assert entries_after_first > 0
    ticks2, admits2, d2h2 = run_once()
    assert eng.jit_cache_entries() == entries_after_first, (
        "a repeated identical workload must not trigger new compilations"
    )
    # per-tick D2H: the [SLOTS] sampled vector; each admission adds the
    # one prefill-sampled token
    assert d2h2 <= ticks2 * SLOTS * 4 + admits2 * 4
    assert d2h2 / max(ticks2, 1) <= (SLOTS + SLOTS) * 4


def test_steady_decode_uploads_no_table_bytes(model):
    """Once admission settles, ticks upload token inputs only: the
    device-resident table is not re-uploaded per tick (the pre-PR
    behaviour was a full [S, W] jnp.asarray every step)."""
    cfg, params = model
    eng = _engine(cfg, params)
    p = RNG.integers(0, cfg.vocab_size, (5,)).astype(np.int32)
    eng.submit(p, 12)
    eng.step()  # admission tick: table rows go up here
    h2d = eng.metrics.counter("serve.bytes.h2d")
    w_bytes = eng._slot_blocks * 4
    deltas = []
    while not eng.scheduler.done():
        before = h2d.value()
        eng.step()
        deltas.append(h2d.value() - before)
    # a tick only pays table bytes when the allocator dirtied a row
    # (block-boundary appends); most steady ticks upload inputs alone
    inputs_only = sum(1 for d in deltas if d <= eng._inputs.size * 4)
    assert inputs_only >= len(deltas) // 2
    assert all(d <= eng._inputs.size * 4 + w_bytes for d in deltas)
