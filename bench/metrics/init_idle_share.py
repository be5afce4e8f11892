"""Share of the traced window in which the device was idle while the host
was inside ``ehfl.init_carry``, each call's eager set-up (%)."""
from bench import spans


def read(ctx):
    if ctx.trace is None or not ctx.trace.ops or not spans.has_spans(ctx.trace):
        return None
    return 100.0 * spans.idle_by_span(ctx.trace)["init_carry"] / ctx.trace.window_s
