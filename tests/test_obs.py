"""Unit tests for the observability subsystem (repro.obs, DESIGN.md §10):
tracer ring buffer + Chrome export, metrics primitives, and the shared
benchmark timing helpers.  Pure host-side — no model, (almost) no jax."""

import json

import pytest

from repro import obs
from repro.obs.metrics import _lkey


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


# ---------------------------------------------------------------------------
# Tracer


def test_span_records_complete_event_with_fake_clock():
    clk = FakeClock()
    tr = obs.Tracer(clock=clk)
    clk.advance(1.0)
    with tr.span("work", uid=7):
        clk.advance(0.25)
    (ev,) = tr.events
    assert (ev.name, ev.ph) == ("work", "X")
    assert ev.ts == pytest.approx(1.0e6)
    assert ev.dur == pytest.approx(0.25e6)
    assert ev.args == {"uid": 7}


def test_begin_end_and_instant_and_counter_events():
    clk = FakeClock()
    tr = obs.Tracer(clock=clk)
    clk.advance(0.5)
    tr.instant("preempt", uid=3)
    tr.counter("sched", pending=2, active=4)
    phs = [e.ph for e in tr.events]
    assert phs == ["i", "C"]
    assert tr.events[0].ts == pytest.approx(0.5e6)
    assert tr.events[0].args == {"uid": 3}
    assert tr.events[1].args == {"pending": 2, "active": 4}
    assert not hasattr(tr, "begin") and not hasattr(tr, "end")


class _Recorder:
    """An annotation sink that logs what was entered and left."""

    def __init__(self):
        self.log = []

    def __call__(self, name, **args):
        rec = self

        class _Ann:
            def __enter__(self):
                rec.log.append(("enter", name, args))

            def __exit__(self, *exc):
                rec.log.append(("exit", name))

        return _Ann()


def test_span_enters_the_annotation_sink_with_scalar_args_only():
    clk = FakeClock()
    sink = _Recorder()
    tr = obs.Tracer(clock=clk, annotate=sink)
    with tr.span("outer", step_num=3):
        with tr.span("inner", uid=7, uids=[1, 2], label="x", share=0.5):
            clk.advance(0.25)
    assert sink.log == [
        ("enter", "outer", {"step_num": 3}),
        # the list stays in the ring buffer only
        ("enter", "inner", {"uid": 7, "label": "x", "share": 0.5}),
        ("exit", "inner"),
        ("exit", "outer"),
    ]
    inner, outer = tr.events  # appended on exit: innermost first
    assert inner.args["uids"] == [1, 2]
    assert (outer.name, outer.args) == ("outer", {"step_num": 3})
    assert inner.dur == pytest.approx(0.25e6)


def test_enable_tracing_installs_the_sink_and_the_null_tracer_has_none():
    sink = _Recorder()
    tr = obs.enable_tracing(capacity=8, annotate=sink)
    assert tr.annotate is sink
    with tr.span("a"):
        pass
    assert [entry[0] for entry in sink.log] == ["enter", "exit"]
    obs.disable_tracing()
    null = obs.get_tracer()
    assert null.annotate is None
    with null.span("b", uid=1):
        pass
    assert len(sink.log) == 2 and null.events == []


def test_async_events_carry_correlation_id():
    tr = obs.Tracer(clock=FakeClock())
    tr.async_begin("request", 42, prompt_len=8)
    tr.async_end("request", 42)
    b, e = tr.events
    assert (b.ph, b.id, e.ph, e.id) == ("b", 42, "e", 42)
    assert b.cat == e.cat == "request"  # async pairs match on (cat, id)


def test_ring_buffer_bounds_memory_and_counts_drops():
    tr = obs.Tracer(capacity=4, clock=FakeClock())
    for i in range(10):
        tr.instant(f"e{i}")
    assert len(tr.events) == 4
    assert tr.dropped == 6
    assert [e.name for e in tr.events] == ["e6", "e7", "e8", "e9"]
    tr.clear()
    assert tr.events == [] and tr.dropped == 0


def test_chrome_trace_schema_and_export(tmp_path):
    clk = FakeClock()
    tr = obs.Tracer(clock=clk)
    with tr.span("prefill", uid=0):
        clk.advance(0.010)
    tr.async_begin("request", 0)
    tr.async_end("request", 0)
    doc = tr.chrome_trace()
    assert set(doc) == {"traceEvents", "displayTimeUnit", "otherData"}
    for row in doc["traceEvents"]:
        # the keys Perfetto's chrome-trace importer requires
        assert {"name", "ph", "ts", "pid"} <= set(row)
    path = tr.export_chrome(str(tmp_path / "trace.json"))
    loaded = json.load(open(path))
    assert loaded["traceEvents"] == doc["traceEvents"]
    x = loaded["traceEvents"][0]
    assert x["ph"] == "X" and x["dur"] == pytest.approx(10_000)  # us


def test_invalid_capacity_rejected():
    with pytest.raises(ValueError, match="capacity"):
        obs.Tracer(capacity=0)


def test_null_tracer_is_free_and_global_swap_roundtrips():
    null = obs.get_tracer()
    assert null is obs.NULL_TRACER and null.enabled is False
    # one shared span object: the disabled hot path allocates nothing
    s1 = null.span("a", uid=1)
    s2 = null.span("b")
    assert s1 is s2
    with s1:
        pass
    null.instant("y")
    null.counter("z", v=1)
    null.async_begin("r", 0)
    null.async_end("r", 0)
    assert null.events == [] and null.chrome_trace()["traceEvents"] == []

    tr = obs.enable_tracing(capacity=16)
    assert obs.get_tracer() is tr and tr.enabled
    obs.disable_tracing()
    assert obs.get_tracer() is obs.NULL_TRACER


# ---------------------------------------------------------------------------
# Metrics


def test_counter_labels_and_monotonicity():
    c = obs.Counter("calls")
    c.inc(op="softmax", impl="pallas")
    c.inc(2, impl="pallas", op="softmax")  # kwarg order must not matter
    c.inc(op="matmul", impl="xla")
    assert c.value(op="softmax", impl="pallas") == 3
    assert c.value(op="matmul", impl="xla") == 1
    assert c.value(op="missing") == 0
    with pytest.raises(ValueError, match="cannot decrease"):
        c.inc(-1)
    snap = c.snapshot()
    assert {"labels": {"impl": "pallas", "op": "softmax"}, "value": 3.0} in snap


def test_label_key_is_order_insensitive():
    assert _lkey({"a": 1, "b": 2}) == _lkey({"b": 2, "a": 1})


def test_gauge_set_inc_dec():
    g = obs.Gauge("depth")
    g.set(5)
    g.inc(2)
    g.dec()
    assert g.value() == 6
    g.set(1, slot=3)
    assert g.value(slot=3) == 1 and g.value() == 6


def test_log_buckets_geometric_and_validated():
    bs = obs.log_buckets(1e-3, 1.0, per_decade=1)
    assert bs == pytest.approx((1e-3, 1e-2, 1e-1, 1.0))
    with pytest.raises(ValueError):
        obs.log_buckets(0, 1)
    with pytest.raises(ValueError):
        obs.log_buckets(1e-3, 1.0, per_decade=0)


def test_histogram_exact_moments_and_percentiles():
    h = obs.Histogram("lat", buckets=(1.0, 2.0, 4.0, 8.0))
    for v in (0.5, 1.5, 1.5, 3.0, 10.0):  # 10.0 lands in the overflow bucket
        h.observe(v)
    snap = h.snapshot()[0]
    assert snap["count"] == 5
    assert snap["sum"] == pytest.approx(16.5)  # sums are exact, not bucketed
    assert snap["min"] == 0.5 and snap["max"] == 10.0
    # p50: rank 2.5 falls in the (1, 2] bucket -> interpolated inside it
    assert 1.0 <= h.percentile(50) <= 2.0
    # p100 == observed max even though the top bucket is unbounded
    assert h.percentile(100) == 10.0
    # percentiles clamp to the observed range
    assert h.percentile(0) >= snap["min"]
    assert h.count() == 5 and h.count(route="other") == 0


def test_histogram_deterministic_and_empty_cases():
    a, b = obs.Histogram("a"), obs.Histogram("b")
    for v in (0.001, 0.02, 0.3, 0.3, 4.0):
        a.observe(v)
        b.observe(v)
    for p in (50, 90, 95, 99):
        assert a.percentile(p) == b.percentile(p)  # same obs -> same estimate
    assert obs.Histogram("e").percentile(50) is None
    with pytest.raises(ValueError, match="percentile"):
        a.percentile(101)
    with pytest.raises(ValueError, match="increase"):
        obs.Histogram("bad", buckets=(2.0, 1.0))


def test_histogram_empty_snapshot_never_leaks_inf_sentinels():
    """A zero-count series holds ±inf min/max init sentinels internally;
    the snapshot must mask both (None), stay JSON-serializable, and the
    percentiles must be None rather than interpolated garbage."""
    h = obs.Histogram("lat")
    h.observe(1.0, route="a")  # a sibling series: 'b' stays empty
    h.count(route="b")  # touch only — count() must not create a series
    snap = {s["labels"].get("route"): s for s in h.snapshot()}
    assert "b" not in snap
    h._get({"route": "b"})  # force an empty series into existence
    snap = {s["labels"].get("route"): s for s in h.snapshot()}
    empty = snap["b"]
    assert empty["count"] == 0 and empty["sum"] == 0.0
    assert empty["min"] is None and empty["max"] is None
    assert empty["p50"] is None and empty["p95"] is None and empty["p99"] is None
    out = json.dumps(snap["b"])  # inf would raise / emit non-JSON
    assert "Infinity" not in out
    assert h.percentile(50, route="b") is None


def test_histogram_single_observation_is_exact_everywhere():
    """One sample: every percentile is that exact value — including a
    sample in the unbounded overflow bucket, where interpolation against
    the +inf upper edge must never run."""
    h = obs.Histogram("lat", buckets=(1.0, 2.0))
    h.observe(7.25)  # overflow bucket: hi edge would be +inf
    for p in (0, 50, 95, 99, 100):
        assert h.percentile(p) == 7.25
    snap = h.snapshot()[0]
    assert snap["min"] == snap["max"] == snap["p50"] == 7.25
    json.dumps(snap)
    # a constant multi-sample series is just as exact
    c = obs.Histogram("const", buckets=(1.0, 2.0))
    for _ in range(5):
        c.observe(0.5)
    assert c.percentile(50) == 0.5 and c.percentile(99) == 0.5


def test_registry_get_or_create_and_kind_conflict():
    reg = obs.MetricsRegistry()
    c = reg.counter("x", help="calls")
    assert reg.counter("x") is c  # get-or-create returns the same instance
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("x")
    reg.gauge("g").set(3)
    reg.histogram("h").observe(0.1)
    snap = reg.snapshot()
    assert set(snap) == {"x", "g", "h"}
    assert snap["g"] == {"kind": "gauge", "series": [{"labels": {}, "value": 3}]}
    assert snap["h"]["series"][0]["count"] == 1
    assert reg.names() == ["g", "h", "x"]
    reg.clear()
    assert reg.snapshot() == {}


def test_default_registry_swap_for_isolation():
    mine = obs.MetricsRegistry()
    prev = obs.set_default_registry(mine)
    try:
        assert obs.default_registry() is mine
    finally:
        obs.set_default_registry(prev)
    assert obs.default_registry() is prev


# ---------------------------------------------------------------------------
# Shared benchmark timing helpers


def test_stopwatch_measures_wall_time():
    from benchmarks._timing import Stopwatch

    with Stopwatch() as sw:
        sum(range(1000))
    assert sw.seconds >= 0.0


def test_time_device_fn_blocks_and_averages():
    import jax.numpy as jnp

    from benchmarks._timing import time_device_fn, time_device_fn_us

    calls = []

    def f():
        calls.append(1)
        return jnp.ones((4,))

    s = time_device_fn(f, iters=3, warmup=2)
    assert s > 0.0
    assert len(calls) == 5  # warmup runs outside the timed region
    assert time_device_fn_us(f, iters=1, warmup=0) == pytest.approx(
        time_device_fn(f, iters=1, warmup=0) * 1e6, rel=5.0
    )
    with pytest.raises(ValueError, match="iters"):
        time_device_fn(f, iters=0)
