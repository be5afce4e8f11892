"""Device time of the eval (predict) executable over the device's busy time
(%).  The executable's name is the traffic file's ``modules.eval`` pattern."""
from bench import trace as tr


def read(ctx):
    if ctx.trace is None or not ctx.trace.ops:
        return None
    busy = tr.busy_s(ctx.trace)
    secs = tr.module_s(ctx.trace, ctx.cell.traffic["modules"]["eval"])
    return 100.0 * secs / (sum(busy.values()) / len(busy)) if secs > 0 else None
