"""Observability wired through the stack (DESIGN.md §10): request-lifecycle
metrics with an injectable fake clock (deterministic TTFT / ITL /
queue-wait, including the paged preempt-and-requeue path), trace export
from a real serve run, dispatch call counters, guard trip events, and the
disabled-tracer no-overhead smoke check."""

import json
import warnings

import jax
import numpy as np
import pytest

from repro import obs, ops
from repro.configs import get_smoke_config
from repro.models.param import materialize
from repro.models.registry import build_model
from repro.serve.engine import ContinuousBatchingEngine, ContinuousConfig

KEY = jax.random.PRNGKey(0)
RNG = np.random.default_rng(0)
MAX_LEN = 40


class FakeClock:
    def __init__(self, t: float = 0.0):
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


@pytest.fixture(autouse=True)
def _clean_globals():
    obs.reset()
    yield
    obs.reset()


def _model_params(arch="granite_8b"):
    cfg = get_smoke_config(arch)
    model = build_model(cfg)
    return cfg, materialize(model.param_specs(), KEY)


def _hist_sum(eng, name):
    (series,) = eng.metrics.snapshot()[name]["series"]
    return series["count"], series["sum"]


# ---------------------------------------------------------------------------
# Request lifecycle with a scripted clock


def test_lifecycle_metrics_deterministic_with_fake_clock():
    cfg, params = _model_params()
    clk = FakeClock()
    eng = ContinuousBatchingEngine(
        cfg, params, ContinuousConfig(num_slots=2, max_len=MAX_LEN),
        clock=clk)
    eng.submit(RNG.integers(0, cfg.vocab_size, (5,)), 3)  # t = 0
    clk.advance(1.0)
    eng.step()  # t=1: admit (queue-wait 1.0), token0 (TTFT 1.0), token1 (ITL 0)
    clk.advance(0.5)
    eng.step()  # t=1.5: token2 (ITL 0.5) -> budget 3 reached, finished
    assert eng.scheduler.done()

    assert _hist_sum(eng, "serve.queue_wait_s") == (1, pytest.approx(1.0))
    assert _hist_sum(eng, "serve.ttft_s") == (1, pytest.approx(1.0))
    assert _hist_sum(eng, "serve.itl_s") == (2, pytest.approx(0.5))
    m = eng.metrics
    assert m.counter("serve.requests.submitted").value() == 1
    assert m.counter("serve.requests.admitted").value() == 1
    assert m.counter("serve.requests.finished").value() == 1
    assert m.counter("serve.requests.preempted").value() == 0
    assert m.counter("serve.tokens.generated").value() == 3
    assert m.gauge("serve.queue.depth").value() == 0
    assert m.gauge("serve.slots.active").value() == 0


def test_queue_wait_measures_backpressure():
    """With one slot, the second request's queue wait spans the first
    request's whole occupancy — the scripted clock pins the exact value."""
    cfg, params = _model_params()
    clk = FakeClock()
    eng = ContinuousBatchingEngine(
        cfg, params, ContinuousConfig(num_slots=1, max_len=MAX_LEN),
        clock=clk)
    prompts = [RNG.integers(0, cfg.vocab_size, (4,)) for _ in range(2)]
    eng.submit(prompts[0], 2)
    eng.submit(prompts[1], 2)
    while not eng.scheduler.done():
        clk.advance(1.0)
        eng.step()
    # r0 admitted at t=1 (wait 1) and finishes that same tick (admission
    # token + decode token = its budget of 2), so r1 admits at t=2: wait 2
    (series,) = eng.metrics.snapshot()["serve.queue_wait_s"]["series"]
    assert series["count"] == 2
    assert series["sum"] == pytest.approx(1.0 + 2.0)
    assert series["max"] == pytest.approx(2.0)


def test_paged_preemption_lifecycle_metrics_and_trace():
    """The preempt-and-requeue path: counters track every eviction, TTFT
    is end-to-end (never re-observed after re-admission), queue-wait
    counts each stint, and the trace shows the preemptions."""
    cfg, params = _model_params()
    clk = FakeClock()
    tracer = obs.Tracer(clock=clk)
    eng = ContinuousBatchingEngine(
        cfg, params,
        ContinuousConfig(num_slots=3, max_len=MAX_LEN,
                         kv_layout="paged", kv_block_size=4,
                         kv_pool_blocks=6),
        tracer=tracer, clock=clk)
    for n, g in zip((7, 9, 5), (8, 7, 6)):
        eng.submit(RNG.integers(0, cfg.vocab_size, (n,)), g)
    while not eng.scheduler.done():
        clk.advance(1.0)
        eng.step()

    m = eng.metrics
    preempted = m.counter("serve.requests.preempted").value()
    assert preempted == eng.preemptions > 0
    assert m.counter("serve.requests.finished").value() == 3
    # every admission stint (first + each re-admission) observes one wait;
    # a victim evicted before its prefill never counted as admitted
    admitted = m.counter("serve.requests.admitted").value()
    assert 3 <= admitted <= 3 + preempted
    assert eng.metrics.histogram("serve.queue_wait_s").count() == admitted
    # TTFT is end-to-end: one observation per request, preemption or not
    assert eng.metrics.histogram("serve.ttft_s").count() == 3
    # block-pool accounting flows through the same registry
    assert m.counter("kv.blocks.allocated").value() > 0
    assert m.counter("kv.blocks.freed").value() == \
        m.counter("kv.blocks.allocated").value()  # drained pool
    assert m.gauge("kv.blocks.used").value() == 0

    events = tracer.events
    assert sum(e.name == "serve.preempt" for e in events) == preempted
    for e in events:
        if e.name == "serve.preempt":
            assert "uid" in e.args and "generated" in e.args
    # the evicted request's tokens straddle the preemption: ITL counts
    # every gap, so total tokens == ttft obs + itl obs
    tokens = m.counter("serve.tokens.generated").value()
    assert eng.metrics.histogram("serve.itl_s").count() == tokens - 3


def test_preempt_restamp_counts_every_queue_stint_exactly_once():
    """Regression for the restamp-on-preempt bug: each wait stint lands
    in serve.queue_wait_s exactly once.  The first stint is observed at
    admission (stamp consumed); preemption opens a *new* stint from the
    eviction time, and re-admission observes exactly that gap — nothing
    lost, nothing double-counted."""
    cfg, params = _model_params()
    clk = FakeClock()
    eng = ContinuousBatchingEngine(
        cfg, params,
        ContinuousConfig(num_slots=1, max_len=MAX_LEN,
                         kv_layout="paged", kv_block_size=4),
        clock=clk)
    eng.submit(RNG.integers(0, cfg.vocab_size, (6,)), 8)  # t = 0
    clk.advance(2.0)
    eng.step()  # admit at t=2: first stint 2.0, stamp consumed
    assert _hist_sum(eng, "serve.queue_wait_s") == (1, pytest.approx(2.0))
    clk.advance(1.0)
    slot = next(s for s in eng.scheduler.slots if not s.free)
    eng._preempt(slot)  # t=3: stint already observed -> restamp to now
    clk.advance(4.0)
    eng.step()  # re-admit at t=7: second stint is 7-3=4, not 7-0=7
    assert _hist_sum(eng, "serve.queue_wait_s") == (2, pytest.approx(6.0))
    while not eng.scheduler.done():
        clk.advance(1.0)
        eng.step()
    count, total = _hist_sum(eng, "serve.queue_wait_s")
    assert total == pytest.approx(6.0)  # no stint observed twice
    assert count == eng.metrics.counter("serve.requests.admitted").value()


def test_preempt_before_admission_observe_keeps_the_original_stint():
    """The other half of the fix: a victim evicted before its admission
    observe ran still carries its original stamp — an unconditional
    restamp would silently drop that whole wait from the histogram."""
    cfg, params = _model_params()
    clk = FakeClock()
    eng = ContinuousBatchingEngine(
        cfg, params,
        ContinuousConfig(num_slots=1, max_len=MAX_LEN,
                         kv_layout="paged", kv_block_size=4),
        clock=clk)
    eng.submit(RNG.integers(0, cfg.vocab_size, (6,)), 3)  # t = 0
    clk.advance(1.0)
    (slot,) = eng.scheduler.admit()  # bound, but not yet observed
    eng._preempt(slot)  # t=1: stamp still pending -> must NOT restamp
    clk.advance(2.0)
    eng.step()  # admit at t=3: the single stint spans the whole wait
    assert _hist_sum(eng, "serve.queue_wait_s") == (1, pytest.approx(3.0))
    while not eng.scheduler.done():
        clk.advance(1.0)
        eng.step()
    assert eng.metrics.histogram("serve.queue_wait_s").count() == \
        eng.metrics.counter("serve.requests.admitted").value()


# ---------------------------------------------------------------------------
# Trace export from a serve run (the acceptance-criterion shape)


def test_serve_trace_has_spans_for_every_request_and_loads_as_chrome_json(
        tmp_path):
    cfg, params = _model_params()
    tracer = obs.Tracer()
    eng = ContinuousBatchingEngine(
        cfg, params, ContinuousConfig(num_slots=2, max_len=MAX_LEN),
        tracer=tracer)
    uids = [eng.submit(RNG.integers(0, cfg.vocab_size, (4 + i,)), 2)
            for i in range(3)]
    eng.run()

    path = tracer.export_chrome(str(tmp_path / "trace.json"))
    doc = json.load(open(path))
    evs = doc["traceEvents"]
    by_name = {}
    for e in evs:
        by_name.setdefault(e["name"], []).append(e)
    # one prefill span per request, carrying its uid
    prefills = by_name["serve.prefill"]
    assert all(p["ph"] == "X" and p["dur"] >= 0 for p in prefills)
    assert sorted(p["args"]["uid"] for p in prefills) == sorted(uids)
    # one decode span per tick, nested in its serve.step span; together
    # they count every decode token (2-token answers: one each) and the
    # rows attended (prompt + the token written: 5, 6, 7)
    decode = by_name["serve.decode"]
    assert all(e["ph"] == "X" for e in decode) and len(decode) == eng.ticks
    assert sum(e["args"]["slots"] for e in decode) == len(uids)
    assert sum(e["args"]["live_rows"] for e in decode) == 5 + 6 + 7
    steps = by_name["serve.step"]
    assert [e["args"]["step_num"] for e in steps] == list(range(eng.steps))
    for e in decode:
        assert any(st["ts"] <= e["ts"]
                   and e["ts"] + e["dur"] <= st["ts"] + st["dur"]
                   for st in steps)
    # one async request track per uid, opened and closed
    req = by_name["request"]
    for uid in uids:
        assert [e["ph"] for e in req if e["id"] == uid] == ["b", "e"]
    # scheduler counter samples rendered as a Perfetto counter track
    assert all(e["ph"] == "C" for e in by_name["serve.sched"])


def test_disabled_tracer_records_nothing_during_serve():
    """The no-op tracer smoke check (CI): a full serve run with tracing
    disabled must leave the global null tracer empty — the hot path
    allocates no events when nobody is recording."""
    cfg, params = _model_params()
    assert obs.get_tracer() is obs.NULL_TRACER
    eng = ContinuousBatchingEngine(
        cfg, params, ContinuousConfig(num_slots=2, max_len=MAX_LEN))
    assert eng.tracer is obs.NULL_TRACER
    outs = eng.serve([RNG.integers(0, cfg.vocab_size, (4,))] * 2, 2)
    assert all(len(o) == 2 for o in outs)
    assert obs.NULL_TRACER.events == []
    assert obs.NULL_TRACER.chrome_trace()["traceEvents"] == []
    # metrics still flow (they are cheap dict ops, not trace allocations)
    assert eng.metrics.counter("serve.requests.finished").value() == 2


def test_engine_stats_merges_metrics_snapshot():
    cfg, params = _model_params()
    eng = ContinuousBatchingEngine(
        cfg, params, ContinuousConfig(num_slots=1, max_len=MAX_LEN))
    eng.submit(RNG.integers(0, cfg.vocab_size, (4,)), 2)
    eng.run()
    st = eng.stats()
    assert st["ticks"] == eng.ticks
    snap = st["metrics"]
    assert snap["serve.requests.finished"]["series"][0]["value"] == 1
    assert snap["serve.ttft_s"]["series"][0]["count"] == 1


# ---------------------------------------------------------------------------
# Dispatch + guard wiring into the global registry / tracer


def test_dispatch_counts_resolved_backend_labels():
    mine = obs.MetricsRegistry()
    prev = obs.set_default_registry(mine)
    try:
        import jax.numpy as jnp

        x = jnp.ones((2, 8))
        ops.softmax(x)  # default spec -> reference
        with ops.use(softmax="xla"):
            ops.softmax(x, kind="exact")  # resolved impl is the override
        c = mine.counter("ops.dispatch.calls")
        here = ops.default_interpret()
        assert c.value(op="softmax", impl="reference", interpret=here) == 1
        assert c.value(op="softmax", impl="xla", interpret=here) == 1
    finally:
        obs.set_default_registry(prev)


def test_guard_trip_increments_counter_and_emits_trace_event():
    mine = obs.MetricsRegistry()
    prev = obs.set_default_registry(mine)
    tracer = obs.enable_tracing()
    try:
        import jax.numpy as jnp

        x = jnp.asarray(RNG.normal(size=(4, 32)) * 4, jnp.float32)
        guard = ops.AccuracyGuard(ops.GuardConfig(tolerance=1e-12))
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", ops.GuardTripWarning)
            ops.softmax(x, ops.SoftmaxSpec(), guard=guard)  # star vs exact
        assert guard.tripped
        c = mine.counter("ops.guard.trips")
        assert c.value(op="softmax", impl="reference") == 1
        assert mine.counter("ops.guard.calls").value(op="softmax") == 1
        assert mine.counter("ops.guard.checks").value(op="softmax") == 1
        assert mine.counter("ops.guard.fallbacks").value(op="softmax") == 1
        trips = [e for e in tracer.events if e.name == "guard.trip"]
        assert len(trips) == 1
        ev = trips[0]
        assert ev.cat == "guard" and ev.args["op"] == "softmax"
        assert ev.args["error"] > ev.args["tolerance"]
        assert ev.args["fallback"] == "reference"
    finally:
        obs.set_default_registry(prev)
        obs.disable_tracing()


def test_engine_guard_counters_reach_engine_stats_and_registry():
    """ContinuousConfig(guard=) + obs: the engine's lifetime guard mirrors
    its counters into the global registry alongside stats()["guard"]."""
    mine = obs.MetricsRegistry()
    prev = obs.set_default_registry(mine)
    try:
        cfg, params = _model_params()
        eng = ContinuousBatchingEngine(
            cfg, params,
            ContinuousConfig(num_slots=1, max_len=MAX_LEN, temperature=0.7,
                             guard=ops.GuardConfig(sample_every=1)))
        eng.submit(RNG.integers(0, cfg.vocab_size, (4,)), 2)
        eng.run()
        st = eng.stats()
        assert st["guard"]["calls"] > 0
        assert mine.counter("ops.guard.calls").value(op="softmax") == \
            st["guard"]["calls"]
    finally:
        obs.set_default_registry(prev)
