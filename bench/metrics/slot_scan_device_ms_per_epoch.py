"""Device time of the slot-level energy scan per simulated epoch (ms): the
chunk's ops scoped ``ehfl.slot_scan``."""
from bench import spans


def read(ctx):
    secs = spans.chunk_scope_s(ctx, ("ehfl.slot_scan",))
    return None if secs is None or not ctx.epochs else 1e3 * secs / ctx.epochs
