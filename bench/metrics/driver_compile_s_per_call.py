"""Seconds per call of the window spent tracing, lowering and compiling or
loading programs from the compilation cache, from JAX's own compile
events: the drivers build a fresh jitted chunk on every call."""


def read(ctx):
    return ctx.compile_s / ctx.n_calls if ctx.n_calls else None
