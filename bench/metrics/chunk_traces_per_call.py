"""Traces of the epoch program per call of the window: the program's count of
``/ehfl/drivers/chunk_trace`` events (one each time JAX traces the chunk)."""
from bench import spans


def read(ctx):
    if ctx.trace is None or not spans.has_spans(ctx.trace) or not ctx.n_calls:
        return None
    return ctx.events.counts.get(spans.CHUNK_TRACE_EVENT, 0) / ctx.n_calls
