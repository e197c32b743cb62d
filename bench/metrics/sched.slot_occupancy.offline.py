"""Share of the slot pool that produced a decode token, per tick, as the
harness saw it in the tick's events; mean over the window's ticks, in %."""


def read(ctx):
    ticks = [s for s in ctx.steps if s.decode_slots > 0]
    if not ticks:
        return None
    return 100.0 * sum(s.decode_slots for s in ticks) / (len(ticks) * ctx.num_slots)
