"""Programs lowered to MLIR per prefill chunk in the traced window (0
once every chunk shape is warm, as the prefill is jitted once per shape):
the engine's ``serve.compile`` lowering events charged to its
``prefill_chunk`` phase (the events behind its
``serve.compile.lowerings{phase=prefill_chunk}`` counter) over its
``serve.prefill_chunk`` calls (those behind ``serve.prefill.chunks``)."""

from bench import program_spans


def read(ctx):
    if ctx.trace is None:
        return None
    return program_spans.lowerings_per_chunk(program_spans.ring())
