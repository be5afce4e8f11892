"""Device time of the jitted epoch-scan executable per simulated epoch (ms).
The executable's name is the traffic file's ``modules.epoch`` pattern."""
from bench import trace as tr


def read(ctx):
    if ctx.trace is None:
        return None
    secs = tr.module_s(ctx.trace, ctx.cell.traffic["modules"]["epoch"])
    return 1e3 * secs / ctx.epochs if secs > 0 else None
