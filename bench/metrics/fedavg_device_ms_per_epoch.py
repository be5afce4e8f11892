"""Device time of FedAvg per simulated epoch (ms): the chunk's ops scoped
``ehfl.fedavg`` (the upload merge and the masked or compacted mean)."""
from bench import spans


def read(ctx):
    secs = spans.chunk_scope_s(ctx, ("ehfl.fedavg",))
    return None if secs is None or not ctx.epochs else 1e3 * secs / ctx.epochs
