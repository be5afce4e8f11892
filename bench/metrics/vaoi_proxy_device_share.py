"""Device time of the VAoI proxy over the device's busy time (%): the
chunk's ops scoped ``ehfl.vaoi_proxy`` (probe forward, Eq. 5 and 7) or
``ehfl.eq6_moment`` (the feature forward of each training step, Eq. 6)."""
from bench import spans
from bench import trace as tr


def read(ctx):
    secs = spans.chunk_scope_s(ctx, ("ehfl.vaoi_proxy", "ehfl.eq6_moment"))
    if secs is None:
        return None
    busy = tr.busy_s(ctx.trace)
    return 100.0 * secs / (sum(busy.values()) / len(busy))
