"""Device-idle milliseconds per engine tick outside the prefill: idle time
inside the engine's ``serve.step`` spans less that inside its
``serve.prefill_chunk`` and ``serve.prefill_finish`` spans, over the
ticks.  The spans are the program's own, moved onto the trace's clock
tick by tick (``bench/program_spans.py``)."""

from bench import program_spans, trace_reduce


def read(ctx):
    spans = program_spans.of(ctx)
    if not spans:
        return None
    return program_spans.idle_outside_prefill_ms_per_tick(
        trace_reduce.union(ctx.trace.ops), spans)
