"""The engine's spans on the trace's clock, the three readers built on
them, the idle gaps named by the innermost span, and the decode tick's
device time by named scope."""

import glob

import numpy as np
import pytest

from bench import common, program_spans, program_trace
from bench import trace_reduce as tr
from bench.readings import Readings
from repro import obs

READERS = ("prefill.idle_in_chunk_share", "prefill.lowerings_per_chunk",
           "sched.idle_outside_prefill_ms_per_tick")


class FakeClock:
    def __init__(self):
        self.t = 100.0  # the ring's clock: its own origin

    def __call__(self):
        return self.t


@pytest.fixture(autouse=True)
def _clean_globals():
    obs.reset()
    yield
    obs.reset()


def two_ticks():
    """A ring buffer of two ticks and the trace the same ticks gave: the
    two clocks have different origins, and each ``bench.step`` starts
    2 ms before its ``serve.step`` and ends with it.

    tick 0 (trace 1.000-1.390): a 200 ms chunk from 1.052, a 50 ms
    finish, a 40 ms decode ending the tick; tick 1 (trace 2.000-2.100): a
    40 ms decode ending the tick.
    """
    clk = FakeClock()
    ring = obs.enable_tracing(clock=clk)
    ring.instant("serve.compile", phase="other", stage="lower", seconds=0.1)
    with ring.span("serve.step", step_num=0):
        clk.t += 0.050
        with ring.span("serve.prefill_chunk", uid=1, tokens=256):
            ring.instant("serve.compile", phase="prefill_chunk",
                         stage="lower", seconds=0.02)
            ring.instant("serve.compile", phase="prefill_chunk",
                         stage="backend", seconds=0.03)
            clk.t += 0.200
        with ring.span("serve.prefill_finish", uid=1):
            clk.t += 0.050
        clk.t += 0.048
        with ring.span("serve.decode", slots=2, live_rows=10):
            clk.t += 0.040
    clk.t += 0.602  # the harness between ticks
    with ring.span("serve.step", step_num=1):
        clk.t += 0.058
        with ring.span("serve.decode", slots=2, live_rows=12):
            clk.t += 0.040
    spans = [("bench.step", 1.000, 1.390), ("bench.step", 2.000, 2.100)]
    # the device: busy through the chunk's last 100 ms and the finish's
    # first 10 ms, and through each decode
    ops = [("%fusion.1", 1.152, 1.262), ("%fusion.2", 1.350, 1.390),
           ("%fusion.3", 2.060, 2.100)]
    return ring, tr.Trace(ops, [], spans)


def ctx_of(trace):
    return Readings(dims={}, num_slots=2, kv_itemsize=2, act_itemsize=2,
                    peaks={}, steps=[], counters={}, trace=trace)


def test_ring_spans_move_onto_the_trace_clock_tick_by_tick():
    ring, trace = two_ticks()
    got = program_spans.aligned(trace.spans, ring.events)
    want = [("serve.step", 1.002, 1.390), ("serve.prefill_chunk", 1.052, 1.252),
            ("serve.prefill_finish", 1.252, 1.302), ("serve.decode", 1.350, 1.390),
            ("serve.step", 2.002, 2.100), ("serve.decode", 2.060, 2.100)]
    assert [g[0] for g in got] == [w[0] for w in want]
    for g, w in zip(got, want):
        assert g[1] == pytest.approx(w[1], abs=1e-9)
        assert g[2] == pytest.approx(w[2], abs=1e-9)


def test_a_ring_that_lost_its_oldest_tick_pairs_from_the_end():
    ring, trace = two_ticks()
    first_tick_end = next(i for i, e in enumerate(ring.events)
                          if e.name == "serve.step")
    got = program_spans.aligned(trace.spans, ring.events[first_tick_end + 1:])
    assert [g[0] for g in got] == ["serve.step", "serve.decode"]
    assert got[0][1] == pytest.approx(2.002, abs=1e-9)


def test_the_three_readers_on_a_hand_made_window():
    ring, trace = two_ticks()
    ctx = ctx_of(trace)
    read = {m: common.metric_reader(m).read(ctx) for m in READERS}
    # the chunk (200 ms) was busy 100 ms of it
    assert read["prefill.idle_in_chunk_share"] == pytest.approx(50.0)
    # one lowering charged to the chunk phase, one chunk
    assert read["prefill.lowerings_per_chunk"] == pytest.approx(1.0)
    # idle in the ticks: 388 - 110 - 40 and 98 - 40 ms; in the chunk and
    # the finish: 100 + 40 ms; (296 - 140) ms over 2 ticks
    assert read["sched.idle_outside_prefill_ms_per_tick"] == pytest.approx(78.0)


def test_readers_read_none_without_the_engine_spans():
    _, trace = two_ticks()
    obs.disable_tracing()  # the ring of a program that records nothing
    assert {m: common.metric_reader(m).read(ctx_of(trace)) for m in READERS} == \
        dict.fromkeys(READERS)
    # a ring with chunk spans but no ticks (an engine without the phase
    # spans and compile accounting): still nothing to read
    old = obs.enable_tracing(clock=FakeClock())
    with old.span("serve.prefill_chunk", uid=1, tokens=8):
        pass
    assert {m: common.metric_reader(m).read(ctx_of(trace)) for m in READERS} == \
        dict.fromkeys(READERS)


def test_idle_gaps_are_named_by_the_innermost_engine_span():
    ring, trace = two_ticks()
    spans = trace.spans + program_spans.aligned(trace.spans, ring.events)
    busy = tr.union(trace.ops)
    gaps = tr.idle_gaps(busy, spans, 1.0, 2.1)
    # between the ticks (its midpoint in no span); the tick's first 152 ms,
    # mostly in the chunk; the 88 ms after the finish, before the decode
    assert [g[0] for g in gaps] == ["host.other", "serve.prefill_chunk", "serve.step"]
    assert [g[1] for g in gaps] == pytest.approx([0.670, 0.152, 0.088])
    # with the harness's spans alone, both tick gaps read bench.step
    assert [g[0] for g in tr.idle_gaps(busy, trace.spans, 1.0, 2.1)] == \
        ["host.other", "bench.step", "bench.step"]


HLO = """\
HloModule jit_tick, entry_computation_layout={...}

%fused_computation.1 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  ROOT %exponential.1 = f32[8]{0} exponential(%p), metadata={op_name="jit(tick)/while/body/attention/exp" source_file="x.py"}
}

ENTRY %main.9 (a: f32[8]) -> f32[8] {
  %fusion.1 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(tick)/while/body/attention/exp" source_file="x.py" source_line=3}
  %fusion.2 = f32[8]{0} fusion(%fusion.1), kind=kLoop, metadata={op_name="jit(tick)/while/body/ffn/dot_general"}
  %convert.3 = bf16[8]{0} convert(%fusion.2), metadata={op_name="jit(tick)/convert_element_type"}
  %convert.5 = bf16[8]{0} convert(%a), backend_config={"flag_configs":[]}
  ROOT %reduce.4 = s32[] reduce(%convert.3), metadata={op_name="jit(tick)/jit(main)/sample/argmax"}
}
"""


def test_tick_ops_are_grouped_by_named_scope():
    scopes = program_trace.op_scopes(HLO)
    assert scopes["fusion.1"] == "attention" and scopes["fusion.2"] == "ffn"
    assert scopes["convert.3"] == "other" and scopes["reduce.4"] == "sample"
    assert scopes["convert.5"] == "other"  # no metadata at all
    modules = [("jit_tick(1)", 0.0, 1.0), ("jit_scan(2)", 2.0, 3.0)]
    ops = [("%while.7", 0.0, 0.5), ("%fusion.1", 0.0, 0.2),
           ("%fusion.2", 0.2, 0.5), ("%convert.3", 0.5, 0.6),
           ("%reduce.4", 0.6, 0.7), ("%copy.9", 0.7, 0.75),
           ("%fusion.1", 2.0, 2.5)]  # outside the tick: not counted
    got = program_trace.tick_scopes(ops, modules, scopes)
    want = {"attention": 0.2, "ffn": 0.3, "other": 0.1, "sample": 0.1,
            "unmapped": 0.05}
    assert got.keys() == want.keys()
    for k in want:
        assert got[k] == pytest.approx(want[k])


def test_engine_ring_lines_up_with_the_profilers_copy(tmp_path):
    """A small engine ticked under a CPU profile: the ring buffer's spans,
    moved by the pairing, land where the profiler put its own copy."""
    import jax
    from jax.profiler import TraceAnnotation

    from repro.configs import get_smoke_config
    from repro.models.param import materialize
    from repro.models.registry import build_model
    from repro.serve.engine import ContinuousBatchingEngine, ContinuousConfig

    cfg = get_smoke_config("granite_8b")
    params = materialize(build_model(cfg).param_specs(), jax.random.PRNGKey(0))
    ring = obs.enable_tracing()
    eng = ContinuousBatchingEngine(
        cfg, params, ContinuousConfig(num_slots=2, max_len=40, kv_layout="paged",
                                      kv_block_size=4, prefill_chunk_tokens=4))
    rng = np.random.default_rng(0)
    for n in (9, 6):
        eng.submit(rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32), 3)
    jax.profiler.start_trace(str(tmp_path))
    ring.clear()
    while not eng.scheduler.done():
        with TraceAnnotation("bench.step"):
            eng.step()
    jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    harness, engine = program_trace.host_spans(path)
    moved = program_spans.aligned(harness, ring.events)
    assert [s[0] for s in moved] == [s[0] for s in engine]
    assert "serve.prefill_chunk" in {s[0] for s in engine}
    for m, e in zip(moved, engine):
        assert abs(m[1] - e[1]) < 2e-3 and abs(m[2] - e[2]) < 2e-3, (m, e)


def test_a_traced_run_of_a_small_cell_reports_the_three_readers():
    """The harness's own traced path on the CPU: the ring buffer holds the
    window's ticks when the readers run, and each reads a number."""
    import copy

    from bench import run

    data = common.BENCH / "tests" / "data"
    bench = copy.deepcopy(common.load_json(data / "tiny-benchmark.json"))
    bench["per_layer"] += [
        {"name": m, "unit": "%", "better": "lower", "source": "program_span",
         "layer": "prefill", "moves": "output_tokens_per_s",
         "workloads": ["tiny-dense.offline"]} for m in READERS]
    opts = run.Options(require_tpu=False,
                       config=common.load_json(data / "tiny-dense.json"),
                       benchmark=bench,
                       mix=common.load_json(data / "tiny-offline.json"))
    args = run.parse_args(["--workload", "tiny-dense.offline", "--seed",
                           "3000000001", "--seconds", "4", "--trace", "1"])
    metrics = run.run_cell(args, opts)["line"]["metrics"]
    # the CPU trace has no device plane: every span reads idle throughout
    assert metrics["prefill.idle_in_chunk_share"]["value"] == pytest.approx(100.0)
    # the prefill is jitted once per chunk shape and the warm-up ran them all
    assert metrics["prefill.lowerings_per_chunk"]["value"] == 0.0
    assert metrics["sched.idle_outside_prefill_ms_per_tick"]["value"] > 0
