"""The paged decode kernel's share of its roofline, in %: least time /
the kernel's device time.

Least time of one call = max(FLOPs / bf16 peak, bytes / HBM peak).  The
count is the work the algorithm needs, the same whatever implements the
call: the K and V rows of live slots only (plus their scale rows on a
quantized pool), one query and one output row per live slot, and
4 x Hq x D FLOPs per live KV row.  Pages a kernel fetches past a slot's
live rows, clamped steps and free slots count nothing.  The decode tick
calls the kernel once per layer.
"""

import re

from bench import trace_reduce

# The kernel as the device trace names it.
KERNEL = re.compile(r"^%?paged_flash_attention")


def call_bytes(dims, kv_itemsize, act_itemsize, decode_slots, live_rows,
               scale_bytes_per_row=0):
    kv = live_rows * (2 * dims["Hkv"] * dims["D"] * kv_itemsize + scale_bytes_per_row)
    qo = decode_slots * 2 * dims["Hq"] * dims["D"] * act_itemsize
    return kv + qo


def call_flops(dims, live_rows):
    return 4 * dims["Hq"] * dims["D"] * live_rows


def least_seconds(dims, peaks, kv_itemsize, act_itemsize, decode_slots,
                  live_rows):
    """(least time of one call, "flops" or "hbm": the bound that sets it)."""
    t_f = call_flops(dims, live_rows) / peaks["bf16_flops"]
    t_b = call_bytes(dims, kv_itemsize, act_itemsize, decode_slots,
                     live_rows) / peaks["hbm_bytes_per_s"]
    return (t_f, "flops") if t_f >= t_b else (t_b, "hbm")


def read(ctx):
    if ctx.trace is None:
        return None
    seconds, n = trace_reduce.total(ctx.trace.ops, lambda name: bool(KERNEL.search(name)))
    if n == 0 or seconds <= 0:
        return None
    least = sum(
        least_seconds(ctx.dims, ctx.peaks, ctx.kv_itemsize, ctx.act_itemsize,
                      s.decode_slots, s.live_rows)[0]
        for s in ctx.steps if s.decode_slots)
    if least <= 0:
        return None
    return 100.0 * ctx.dims["L"] * least / seconds
