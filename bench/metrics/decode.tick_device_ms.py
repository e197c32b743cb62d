"""Mean device milliseconds of one execution of the engine's decode tick
(``jit_tick``: the whole pool's decode step and sampling)."""

from bench import trace_reduce


def read(ctx):
    if ctx.trace is None:
        return None
    seconds, n = trace_reduce.total(ctx.tick_modules())
    return seconds * 1e3 / n if n else None
