"""Device time of local training per simulated epoch (ms): the chunk's ops
scoped ``ehfl.local_train`` (the dense vmap and its merges, or the compacted
slab's gather, training and scatter; the Eq. 6 moment inside it included)."""
from bench import spans


def read(ctx):
    secs = spans.chunk_scope_s(ctx, ("ehfl.local_train",))
    return None if secs is None or not ctx.epochs else 1e3 * secs / ctx.epochs
