"""Useful model operations per second of the traced window over the chips'
bf16 peak (%): training of the clients that started (the program's own
``n_started``), VAoI's feature and probe forwards, and the eval forwards,
counted from the configuration's shapes (``bench/flops.py``)."""
from bench import flops, peaks


def read(ctx):
    if ctx.trace is None or not ctx.trace.ops:
        return None
    cfg, traffic = ctx.cell.config, ctx.cell.traffic
    every = cfg["sim"]["eval_every"]
    evals = ctx.n_calls * -(-traffic["horizon"] // every)
    ops = flops.window_flops(cfg["model"], cfg["sim"], cfg["data"], traffic["policy"],
                             n_started=ctx.n_started, epochs=ctx.epochs, evals=evals)
    peak = peaks.peak(ctx.device["kind"])["bf16_flops_per_s"]
    return 100.0 * ops / (ctx.window_s * ctx.chips * peak)
