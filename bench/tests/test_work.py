"""The operation and byte counts behind decode.mfu and
paged_decode_roofline, against hand counts at the two configurations'
published widths."""

import numpy as np
import pytest

from bench import common, peaks
from bench.correct import reference_decoder

MFU = common.metric_reader("decode.mfu")
ROOF = common.metric_reader("paged_decode_roofline")


def dims(name):
    return reference_decoder.dims_of(common.load_config(name)["model"])


def test_granite_8b_matmul_params_per_token():
    # attention 2*4096*4096 + 2*4096*1024, FFN 3*4096*14336, 8 layers,
    # output head 4096*49152
    per_layer = 41_943_040 + 176_160_768
    assert MFU.matmul_params_per_token(dims("granite-8b-d8")) == \
        8 * per_layer + 201_326_592 == 1_946_157_056


def test_granite_moe_matmul_params_count_top_8_of_32_experts():
    # attention 2*1024*1024 + 2*1024*512, router 1024*32, 8 experts of
    # 3*1024*512, 24 layers, output head 1024*49155
    per_layer = 3_145_728 + 32_768 + 8 * 1_572_864
    assert MFU.matmul_params_per_token(dims("granite-moe-1b-a400m")) == \
        24 * per_layer + 50_334_720 == 428_608_512


def test_matmul_params_agree_with_the_system_param_shapes():
    """Counted from the system's ParamSpec shapes: every matrix a token
    multiplies, experts scaled by K/E."""
    from bench.run import system_config
    from repro.models.param import shape_tree
    from repro.models.registry import build_model

    for name in ("granite-8b-d8", "granite-moe-1b-a400m"):
        config = common.load_config(name)
        d = reference_decoder.dims_of(config["model"])
        cfg = system_config(config, reference_decoder)
        tree = shape_tree(build_model(cfg).param_specs())
        b = tree["blocks"]
        n = sum(int(np.prod(b["attn"][k].shape)) for k in ("wq", "wk", "wv", "wo"))
        if "moe" in b:
            n += int(np.prod(b["moe"]["router"].shape))
            n += sum(int(np.prod(b["moe"][k].shape)) for k in ("wi", "wg", "wo")) \
                * d["K"] // d["E"]
        else:
            n += sum(int(np.prod(b["mlp"][k].shape)) for k in ("wi", "wg", "wo"))
        n += d["d"] * d["V"]  # the head's vocabulary columns, padding excluded
        assert MFU.matmul_params_per_token(d) == n


def test_tick_flops_add_attention_over_live_rows():
    d = dims("granite-8b-d8")
    f = MFU.tick_flops(d, decode_slots=3, live_rows=600)
    assert f == 2 * 1_946_157_056 * 3 + 8 * 4 * 32 * 128 * 600


def test_paged_kernel_work_at_granite_8b_widths():
    d = dims("granite-8b-d8")
    # 3 live slots of 100, 200, 300 rows: K+V of a row 2*8*128*2 B,
    # q and out of a slot 2*32*128*2 B, 4*32*128 FLOPs per row
    assert ROOF.call_bytes(d, 2, 2, 3, 600) == 600 * 4096 + 3 * 16384
    assert ROOF.call_flops(d, 600) == 600 * 16384
    t, bound = ROOF.least_seconds(d, peaks.peaks("TPU v5 lite"), 2, 2, 3, 600)
    assert bound == "hbm" and t == pytest.approx(2_506_752 / 819e9)


def test_paged_kernel_work_at_granite_moe_widths():
    d = dims("granite-moe-1b-a400m")
    # K+V of a row 2*8*64*2 B, q and out of a slot 2*16*64*2 B
    assert ROOF.call_bytes(d, 2, 2, 32, 32 * 700) == 32 * 700 * 2048 + 32 * 4096
    assert ROOF.call_flops(d, 1) == 4 * 16 * 64


def test_kernel_and_gather_adapter_are_given_the_same_work():
    """The system's own traffic model counts different bytes for the two
    implementations of one call (the adapter reads whole table windows);
    the roofline's count depends on the live rows alone."""
    from repro.ops import paged_gather_bytes

    d = dims("granite-8b-d8")
    live = np.array([100, 0, 300, 17])
    call = dict(table_width=96, block_size=16, live_lens=live,
                num_kv_heads=d["Hkv"], head_dim=d["D"], dtype_bytes=2)
    assert paged_gather_bytes("pallas_paged", **call) != \
        paged_gather_bytes("xla", **call)
    slots, rows = int(np.count_nonzero(live)), int(live.sum())
    work = {impl: (ROOF.call_bytes(d, 2, 2, slots, rows), ROOF.call_flops(d, rows))
            for impl in ("pallas_paged", "xla")}
    assert work["pallas_paged"] == work["xla"]
    assert work["xla"][0] == rows * 4096 + slots * 16384
