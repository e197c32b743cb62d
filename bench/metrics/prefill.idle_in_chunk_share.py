"""Share of the engine's ``serve.prefill_chunk`` spans in which no
operation ran on the device, in %: 1 - union of busy intervals / span
time.  The spans are the program's own, moved onto the trace's clock
tick by tick (``bench/program_spans.py``)."""

from bench import program_spans, trace_reduce


def read(ctx):
    spans = program_spans.of(ctx)
    if not spans:
        return None
    return program_spans.idle_in_chunk_share(trace_reduce.union(ctx.trace.ops), spans)
