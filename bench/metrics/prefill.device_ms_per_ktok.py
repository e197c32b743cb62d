"""Device milliseconds per thousand prompt tokens prefilled: every program
the engine runs outside its decode tick, pool write, table push and slot
reset (the chunked prefill's ``jit_prefill`` and ``jit_prefill_extend``,
one program per chunk shape, and the first token's sampling), over the prompt tokens of the engine's own
``serve.prefill_chunk`` spans in the traced window."""

from bench import trace_reduce


def read(ctx):
    tokens = ctx.counters.get("prefill_tokens", 0)
    if ctx.trace is None or tokens <= 0:
        return None
    seconds, n = trace_reduce.total(ctx.prefill_modules())
    if n == 0:
        return None
    return seconds * 1e3 / (tokens / 1e3)
