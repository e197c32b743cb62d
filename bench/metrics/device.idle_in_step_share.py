"""Share of the host's ``bench.step`` spans in which no operation ran on
the device, in %: 1 - union of busy intervals / span time."""

from bench import trace_reduce


def read(ctx):
    if ctx.trace is None:
        return None
    spans = [s for s in ctx.trace.spans if s[0] == "bench.step"]
    share = trace_reduce.idle_share(trace_reduce.union(ctx.trace.ops), spans)
    return None if share is None else 100.0 * share
