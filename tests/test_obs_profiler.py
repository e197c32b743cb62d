"""The engine's tick phases and compiles on the profiler's clock
(DESIGN.md §10): the tracer's annotation sink puts ``serve.*`` spans in a
``jax.profiler`` trace, nested in the tick's step annotation; the
disabled tracer builds no annotation at all; one process-wide compile
listener charges lowerings and compile seconds to the engine's open
phase, and never keeps an engine alive."""

import gc
import glob
import weakref

import jax
import numpy as np
import pytest
from jax._src import monitoring as jax_monitoring
from jax.profiler import ProfileData

from repro import obs
from repro.configs import get_smoke_config
from repro.models.param import materialize
from repro.models.registry import build_model
from repro.serve import engine as engine_mod
from repro.serve.engine import ContinuousBatchingEngine, ContinuousConfig

KEY = jax.random.PRNGKey(0)
MAX_LEN = 40


@pytest.fixture(autouse=True)
def _clean_globals():
    obs.reset()
    yield
    obs.reset()


@pytest.fixture(scope="module")
def model():
    cfg = get_smoke_config("granite_8b")
    return cfg, materialize(build_model(cfg).param_specs(), KEY)


def _chunked(cfg, params, **kw):
    return ContinuousBatchingEngine(
        cfg, params,
        ContinuousConfig(num_slots=2, max_len=MAX_LEN, kv_layout="paged",
                         kv_block_size=4, prefill_chunk_tokens=4),
        **kw)


def _prompt(cfg, n, seed=0):
    return np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (n,)).astype(np.int32)


def _host_spans(path):
    """``(name, start_s, end_s, stats)`` of the host planes' ``serve.*``
    events, as the benchmark's trace reduction reads an xplane."""
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("serve."):
                    t0 = e.start_ns * 1e-9
                    out.append((e.name, t0, t0 + e.duration_ns * 1e-9,
                                dict(e.stats)))
    return sorted(out, key=lambda e: e[1])


def test_engine_spans_reach_the_profiler_trace_nested_in_the_step(
        model, tmp_path):
    cfg, params = model
    tracer = obs.Tracer()
    eng = _chunked(cfg, params, tracer=tracer)
    assert tracer.annotate is engine_mod.profiler_annotation
    eng.submit(_prompt(cfg, 10), 3)
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.run()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    spans = _host_spans(path)
    by_name = {}
    for s in spans:
        by_name.setdefault(s[0], []).append(s)

    steps = by_name["serve.step"]
    assert [s[3]["step_num"] for s in steps] == list(range(eng.steps))
    # 10 prompt tokens at a budget of 4: chunks of 4, 4, 2
    chunks = by_name["serve.prefill_chunk"]
    assert [s[3]["tokens"] for s in chunks] == [4, 4, 2]
    assert all(s[3]["total"] == 10 and "uid" in s[3] for s in chunks)
    (admit,) = by_name["serve.admit"]
    assert admit[3]["admitted"] == 1
    (finish,) = by_name["serve.prefill_finish"]
    decode = by_name["serve.decode"]
    assert len(decode) == 2  # tokens 2 and 3 of the answer
    # rows attended: the 10 prompt rows + the token each tick writes
    assert [(s[3]["slots"], s[3]["live_rows"]) for s in decode] == \
        [(1, 11), (1, 12)]
    for child in [admit, finish] + chunks + decode + by_name["serve.blocks"]:
        assert any(st[1] <= child[1] and child[2] <= st[2] for st in steps), \
            child[0]
    # the ring buffer holds the same spans
    ring = [e.name for e in tracer.events if e.ph == "X"]
    assert ring.count("serve.step") == len(steps)
    assert ring.count("serve.prefill_chunk") == 3


def test_disabled_tracer_builds_no_annotation(model, monkeypatch):
    cfg, params = model
    calls = []

    def counting(name, **args):
        calls.append(name)
        return obs.NULL_TRACER.span(name)

    monkeypatch.setattr(engine_mod, "profiler_annotation", counting)
    eng = _chunked(cfg, params)
    assert eng.tracer is obs.NULL_TRACER
    eng.submit(_prompt(cfg, 10), 3)
    eng.run()
    assert calls == [] and obs.NULL_TRACER.events == []
    # the same engine path with a recording tracer does call the sink
    tracer = obs.Tracer()
    eng = _chunked(cfg, params, tracer=tracer)
    assert tracer.annotate is counting
    eng.submit(_prompt(cfg, 10), 3)
    eng.run()
    assert calls.count("serve.step") == eng.steps > 0
    assert calls.count("serve.prefill_chunk") == 3


def test_compiles_are_charged_to_the_open_phase(model):
    cfg, params = model
    eng = _chunked(cfg, params)
    lowerings = eng.metrics.counter("serve.compile.lowerings")
    seconds = eng.metrics.counter("serve.compile.seconds")
    chunks = eng.metrics.counter("serve.prefill.chunks")
    # a prompt length no other test uses: its first chunk's shape is new
    eng.submit(_prompt(cfg, 13), 12)
    eng.step()  # admit + the first 4-token chunk
    assert chunks.value() == 1
    assert lowerings.value(phase="prefill_chunk") >= 1
    assert seconds.value(phase="prefill_chunk") > 0
    while eng.ticks < 2:  # the rest of the prompt, the first decode ticks
        eng.step()
    decode0 = lowerings.value(phase="decode")
    before = eng.ticks
    for _ in range(6):  # steady decode: every program is compiled
        eng.step()
    assert eng.ticks == before + 6
    assert lowerings.value(phase="decode") == decode0
    assert eng.phase == "other"
    # an eager computation outside step() is charged to ``other``
    other0 = lowerings.value(phase="other")
    jax.jit(lambda x: x * 3 + 1)(np.arange(7.0))
    assert lowerings.value(phase="other") == other0 + 1
    snap = eng.stats()["metrics"]
    assert {"serve.compile.lowerings", "serve.compile.seconds",
            "serve.prefill.chunks"} <= set(snap)


def test_compile_instants_mark_the_trace_when_recording(model):
    cfg, params = model
    tracer = obs.Tracer()
    eng = _chunked(cfg, params, tracer=tracer)
    tracer.clear()
    eng.submit(_prompt(cfg, 11), 2)
    eng.step()
    marks = [e.args for e in tracer.events if e.name == "serve.compile"]
    lowered = eng.metrics.counter("serve.compile.lowerings").value(
        phase="prefill_chunk")
    assert sum(1 for m in marks if m["stage"] == "lower"
               and m["phase"] == "prefill_chunk") == lowered >= 1
    assert any(m["stage"] == "backend" for m in marks)
    # jaxpr traces (dozens per eager prefill) add seconds, not markers
    assert {m["stage"] for m in marks} <= {"lower", "backend"}
    assert all(m["seconds"] >= 0 for m in marks)


def test_one_compile_listener_for_many_engines_and_none_kept_alive(model):
    cfg, params = model
    engines = [ContinuousBatchingEngine(
        cfg, params, ContinuousConfig(num_slots=1, max_len=MAX_LEN))
        for _ in range(20)]
    listeners = jax_monitoring._event_duration_secs_listeners
    assert listeners.count(engine_mod._on_compile) == 1
    ref = weakref.ref(engines[-1])
    del engines
    gc.collect()
    assert ref() is None  # the listener holds engines weakly
    jax.jit(lambda x: x - 5)(np.arange(3.0))  # charged to nobody: no error
