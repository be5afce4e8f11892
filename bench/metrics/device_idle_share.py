"""Share of the traced window in which no operation ran on the device (%),
averaged over the cell's chips."""
from bench import trace as tr


def read(ctx):
    if ctx.trace is None or not ctx.trace.ops:
        return None
    busy = tr.busy_s(ctx.trace)
    return 100.0 * (1.0 - sum(busy.values()) / len(busy) / ctx.trace.window_s)
