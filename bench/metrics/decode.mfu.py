"""Model FLOP/s utilization of the decode tick, in %: the FLOPs the model
needs for the ticks' live tokens over (tick device time x the chip's
bf16 peak).

Per live token: 2 x the matmul parameters one token uses (attention
projections, the dense FFN or the router plus the top-K experts, the
output head; the embedding lookup and the norms do no matmul), plus the
attention scores and values over the slot's live rows, 4 x Hq x D per row
in every layer.  Free slots, padding and the experts a token did not
choose count nothing.
"""

from bench import trace_reduce


def matmul_params_per_token(dims):
    d, hq, hkv, dd, f = dims["d"], dims["Hq"], dims["Hkv"], dims["D"], dims["f"]
    attn = 2 * d * hq * dd + 2 * d * hkv * dd
    if dims["family"] == "moe":
        ffn = d * dims["E"] + dims["K"] * 3 * d * f
    else:
        ffn = 3 * d * f
    return dims["L"] * (attn + ffn) + d * dims["V"]


def tick_flops(dims, decode_slots, live_rows):
    attention = dims["L"] * 4 * dims["Hq"] * dims["D"] * live_rows
    return 2 * matmul_params_per_token(dims) * decode_slots + attention


def read(ctx):
    if ctx.trace is None:
        return None
    seconds, n = trace_reduce.total(ctx.tick_modules())
    if n == 0 or seconds <= 0:
        return None
    flops = sum(tick_flops(ctx.dims, s.decode_slots, s.live_rows)
                for s in ctx.steps)
    if flops <= 0:
        return None
    return 100.0 * flops / (seconds * ctx.peaks["bf16_flops"])
