"""Share of the traced window in which the device was idle while the host
was inside an ``ehfl.chunk`` span that traced the epoch program again (%):
the per-call trace, lowering and cache load of the chunk."""
from bench import spans


def read(ctx):
    if ctx.trace is None or not ctx.trace.ops or not spans.has_spans(ctx.trace):
        return None
    return 100.0 * spans.idle_by_span(ctx.trace)["chunk_traced"] / ctx.trace.window_s
