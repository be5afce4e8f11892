"""Share of the traced window in which the device was idle while the host
was inside ``ehfl.eval`` or an ``ehfl.chunk`` that did not trace (%): the
host round trip of every ``eval_every`` chunk."""
from bench import spans


def read(ctx):
    if ctx.trace is None or not ctx.trace.ops or not spans.has_spans(ctx.trace):
        return None
    idle = spans.idle_by_span(ctx.trace)
    return 100.0 * (idle["eval"] + idle["chunk_untraced"]) / ctx.trace.window_s
