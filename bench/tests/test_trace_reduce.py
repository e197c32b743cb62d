"""The trace reduction: interval arithmetic on hand-made events, and the
reading of a trace recorded on the chip."""

import gzip

import pytest

from bench import common
from bench import trace_reduce as tr


def test_union_merges_overlaps_and_keeps_gaps():
    ev = [("a", 0.0, 1.0), ("b", 0.5, 2.0), ("c", 3.0, 4.0), ("d", 3.5, 3.6)]
    assert tr.union(ev) == [(0.0, 2.0), (3.0, 4.0)]


def test_idle_share_over_host_spans():
    busy = [(0.0, 2.0), (3.0, 4.0)]
    spans = [("bench.step", 0.0, 2.5), ("bench.step", 2.5, 5.0)]
    # busy inside the spans: 2 + 1 of 5 seconds
    assert abs(tr.idle_share(busy, spans) - 0.4) < 1e-12
    assert tr.idle_share(busy, []) is None


def test_idle_gaps_are_named_by_the_innermost_host_span():
    busy = [(0.0, 1.0), (1.2, 2.0), (3.0, 4.0)]
    spans = [("bench.step", 0.9, 2.1), ("bench.wait_arrival", 2.1, 3.0),
             ("bench.submit", 1.05, 1.15)]
    gaps = tr.idle_gaps(busy, spans, 0.0, 4.5)
    assert gaps[0][0] == "bench.wait_arrival" and abs(gaps[0][1] - 1.0) < 1e-12
    assert gaps[1][0] == "host.other" and abs(gaps[1][1] - 0.5) < 1e-12
    assert gaps[2][0] == "bench.submit" and abs(gaps[2][1] - 0.2) < 1e-12


def test_totals_and_top_ops():
    ev = [("jit_tick(1)", 0.0, 0.03), ("jit_tick(1)", 0.05, 0.08),
          ("jit_scan(2)", 0.1, 0.3)]
    assert tr.total(ev, lambda n: n.startswith("jit_tick")) == (
        0.03 + 0.03, 2)
    top = tr.top_ops(ev, 1)
    assert top[0][0] == "jit_scan(2)" and abs(top[0][1] - 0.2) < 1e-12


# A trace recorded on a TPU v5 lite: two seconds of the MoE offline cell
# (granite-moe-1b-a400m, 24 layers, 32 busy slots), gzipped.
RECORDED = common.BENCH / "testdata" / "moe-offline-2s.xplane.pb.gz"


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "moe.xplane.pb"
    path.write_bytes(gzip.decompress(RECORDED.read_bytes()))
    return tr.load(str(path))


def test_recorded_trace_holds_the_ticks_kernels_and_host_spans(recorded):
    ticks = [m for m in recorded.modules if m[0].startswith("jit_tick")]
    kernels = [o for o in recorded.ops if o[0].startswith("%paged_flash_attention")]
    assert len(ticks) == 29
    assert [s[0] for s in recorded.spans] == ["bench.step"] * 29
    # one paged-kernel call per layer per tick, each inside its tick
    assert len(kernels) == 29 * 24
    assert all(any(ta <= a and b <= tb for _, ta, tb in ticks) for _, a, b in kernels)
    # each tick runs inside a host step span
    assert all(any(sa <= a and b <= sb for _, sa, sb in recorded.spans)
               for _, a, b in ticks)


def test_recorded_trace_reduces_to_the_numbers_read_by_hand(recorded):
    # summed from the trace's "XLA Modules" and "XLA Ops" lines directly
    tick_s, n = tr.total(recorded.modules, lambda m: m.startswith("jit_tick"))
    assert n == 29 and tick_s == pytest.approx(1.893146707, abs=1e-9)
    kern_s, k = tr.total(recorded.ops, lambda o: o.startswith("%paged_flash_attention"))
    assert k == 696 and kern_s == pytest.approx(0.644613997, abs=1e-9)
    busy = tr.union(recorded.ops)
    a, b = recorded.spans[0][1], recorded.spans[-1][2]
    assert b - a == pytest.approx(2.060631952, abs=1e-9)
    # the ops' union and the programs' union are two readings of busy
    assert tr.covered(busy, a, b) == pytest.approx(
        tr.covered(tr.union(recorded.modules), a, b), rel=1e-3)
    assert 100 * tr.idle_share(busy, recorded.spans) == pytest.approx(8.0138, abs=1e-3)
    # leaf ops: the layer loop (%while) holds the kernel and is left out
    top = tr.top_ops(recorded.ops, 3)
    assert top[0][0] == "%paged_flash_attention.13"
    assert top[0][1] == pytest.approx(kern_s, abs=1e-9)
    assert not any(name.startswith("%while") for name, _ in tr.top_ops(recorded.ops))
    assert sum(b_ - a_ for _, a_, b_ in tr.leaves(recorded.ops)) <= tr.covered(busy, -1e9, 1e9) + 1e-6


def test_metric_readers_on_the_recorded_trace(recorded):
    from bench import peaks
    from bench.correct import reference_decoder
    from bench.drive import Step
    from bench.readings import Readings

    dims = reference_decoder.dims_of(common.load_config("granite-moe-1b-a400m")["model"])
    # 29 ticks of 32 decoding slots, 700 live rows each (a stand-in count)
    steps = [Step(0.0, 0.0, 32, 32 * 700) for _ in range(29)]
    ctx = Readings(dims=dims, num_slots=32, kv_itemsize=2, act_itemsize=2,
                   peaks=peaks.peaks("TPU v5 lite"), steps=steps, counters={},
                   trace=recorded)
    read = {m: common.metric_reader(m).read(ctx) for m in (
        "decode.tick_device_ms", "paged_decode_roofline", "decode.mfu",
        "device.idle_in_step_share")}
    assert read["decode.tick_device_ms"] == pytest.approx(1893.146707 / 29)
    # a call: 22,400 rows x (2 x 8 x 64 x 2 B) + 32 x (2 x 16 x 64 x 2 B)
    # = 46,006,272 B, HBM-bound at 819 GB/s; 24 calls a tick, 29 ticks
    least = 29 * 24 * 46_006_272 / 819e9
    assert read["paged_decode_roofline"] == pytest.approx(100 * least / 0.644613997)
    # 32 tokens x 2 x 428,608,512 params + 24 x 4 x 16 x 64 x 22,400 rows
    flops = 29 * (32 * 2 * 428_608_512 + 24 * 4 * 16 * 64 * 22_400)
    assert read["decode.mfu"] == pytest.approx(100 * flops / (1.893146707 * 197e12))
    assert read["device.idle_in_step_share"] == pytest.approx(8.0138, abs=1e-3)
    assert common.metric_reader("prefill.device_ms_per_ktok").read(ctx) is None
