"""Retrace and transfer regression tests (DESIGN.md §11).

The device-resident engine tick makes two quantitative promises:

* **Bounded retraces** — admission shapes are bucketed to powers of two
  (``serve.paged.bucket_blocks``), so a mixed-length paged workload
  compiles O(log W) admission-write variants, not one per block count;
  and a *repeated* workload compiles nothing at all.
* **Prompt programs compiled once** — the model's ``prefill`` and
  ``prefill_extend`` are jitted per model instance, so a chunk of a
  ``(length, staging rows, capacity)`` seen before lowers nothing, whether
  the engine or a direct call (the benchmark's warm-up) saw it first.
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
import jax.numpy as jnp

from repro import ops
from repro.configs import get_smoke_config
from repro.core.scan_ctl import unroll_scans
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


def _prefill_lowerings(eng):
    return eng.metrics.counter("serve.compile.lowerings").value(
        phase="prefill_chunk")


def test_repeat_chunked_prefill_lowers_nothing(model):
    """A second identical chunked-prefill workload on the SAME engine
    lowers no program while a chunk runs, and adds no jit entry — the
    prompt programs are counted among the engine's entries."""
    cfg, params = model
    eng = _engine(cfg, params, prefill_chunk_tokens=4)
    prompts, gens = _mixed_workload(cfg, n=8)

    def run_once():
        for p, g in zip(prompts, gens):
            eng.submit(p, g)
        eng.run()

    run_once()
    entries = eng.jit_cache_entries()
    programs = sum(f._cache_size() for f in eng.model.prompt_programs)
    assert programs >= 2  # a first chunk and an extend at least
    chunks0 = eng.metrics.counter("serve.prefill.chunks").value()
    lowered0 = _prefill_lowerings(eng)
    assert lowered0 >= programs
    run_once()
    assert eng.metrics.counter("serve.prefill.chunks").value() > chunks0
    assert _prefill_lowerings(eng) == lowered0
    assert eng.jit_cache_entries() == entries


@pytest.mark.parametrize("arch", ["granite_8b", "granite_moe_1b_a400m"])
def test_direct_model_calls_warm_the_engine_chunks(arch):
    """Calling ``model.prefill`` / ``prefill_extend`` directly, as the
    benchmark's warm-up does, with each later chunk's ``(chunk, staging
    rows, capacity)`` leaves the engine's chunks nothing to lower; and an
    extend may run twice on one staging cache (nothing is donated)."""
    cfg = get_smoke_config(arch)
    params = materialize(build_model(cfg).param_specs(), KEY)
    eng = _engine(cfg, params, prefill_chunk_tokens=4)
    prompt = RNG.integers(0, cfg.vocab_size, (10,)).astype(np.int32)
    ts = eng._staging_rows(len(prompt))
    cap = eng.model.moe_prefill_capacity(len(prompt))
    assert (cap is None) == (arch == "granite_8b")
    m = eng.model

    def chunk(n):
        return jnp.asarray(np.zeros(n, np.int32))[None]

    # 10 tokens at a budget of 4: a first chunk of 4, extends of 4 and 2
    _, base = m.prefill(eng.params, chunk(4), eng.cb.max_len, cache_t=ts,
                        moe_capacity=cap)
    first = m.prefill_extend(eng.params, base, chunk(4), moe_capacity=cap)
    again = m.prefill_extend(eng.params, base, chunk(4), moe_capacity=cap)
    for a, b in zip(jax.tree.leaves(first), jax.tree.leaves(again)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    m.prefill_extend(eng.params, base, chunk(2), moe_capacity=cap)
    entries = eng.jit_cache_entries()

    eng.submit(prompt, 3)
    eng.step()  # admission + the first chunk
    assert eng.metrics.counter("serve.prefill.chunks").value() == 1
    eng.run()
    assert eng.metrics.counter("serve.prefill.chunks").value() == 3
    assert _prefill_lowerings(eng) == 0
    assert sum(f._cache_size() for f in m.prompt_programs) == 3
    assert eng.jit_cache_entries() > entries  # the tick and pool writes


def test_prompt_programs_trace_anew_under_another_context(model):
    """Overrides and the scan-unroll probe flag resolve at trace time, so
    they key the prompt programs' cache: a call under another context
    compiles its own program, a call under a seen one reuses it."""
    cfg, params = model
    m = build_model(cfg)
    toks = jnp.asarray(RNG.integers(0, cfg.vocab_size, (1, 6)), jnp.int32)
    size = m.prompt_programs[0]._cache_size

    want, _ = m.prefill(params, toks, 8)
    m.prefill(params, toks, 8)
    assert size() == 1
    with ops.use(softmax="xla"):
        m.prefill(params, toks, 8)
    assert size() == 2
    with unroll_scans():
        got, _ = m.prefill(params, toks, 8)
    assert size() == 3
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    m.prefill(params, toks, 8)
    assert size() == 3


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
