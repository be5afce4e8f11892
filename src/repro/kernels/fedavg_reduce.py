"""Pallas TPU kernel: weighted FedAvg aggregation.

out[p] = sum_k weights[k] * msgs[k, p] — K client update vectors of length P
reduced into the new global.  Pure bandwidth (no MXU): tiles of (BK, BP)
stream through VMEM; the P axis is the parallel grid dim, K is reduced with a
VMEM fp32 accumulator so bf16 messages aggregate without precision loss.

Every block is 2-D and either spans the whole array dimension or is aligned
to the TPU tiling (BK a multiple of 8/16 sublanes, BP of 128 lanes): the
weights ride as a (K, 1) column and the result as a (1, P) row, since Mosaic
refuses 1-D blocks that are not multiples of 128.  Ragged edges are NOT
padded in HBM (a pad would copy the whole (K, P) message matrix): the grid
runs ``cdiv`` blocks, out-of-range message rows are masked to zero in the
kernel, and out-of-range output lanes are dropped by the masked store.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _make_kernel(K: int, bk: int):
    def _kernel(msgs_ref, w_ref, out_ref, acc_ref):
        kblk = pl.program_id(1)
        nk = pl.num_programs(1)

        @pl.when(kblk == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        m = msgs_ref[...].astype(jnp.float32)  # (BK, BP)
        w = w_ref[...].astype(jnp.float32)  # (BK, 1)
        prod = m * w
        if K % bk:  # ragged last K block: rows past K hold garbage
            rows = kblk * bk + jax.lax.broadcasted_iota(jnp.int32, prod.shape, 0)
            prod = jnp.where(rows < K, prod, 0.0)
        acc_ref[...] += jnp.sum(prod, axis=0, keepdims=True)

        @pl.when(kblk == nk - 1)
        def _finalize():
            out_ref[...] = acc_ref[...]

    return _kernel


@functools.partial(jax.jit, static_argnames=("block_k", "block_p", "interpret"))
def fedavg_reduce(
    msgs: jax.Array,
    weights: jax.Array,
    *,
    block_k: int = 64,
    block_p: int = 2048,
    interpret: bool = False,
) -> jax.Array:
    """msgs: (K, P); weights: (K,) -> (P,) fp32 weighted sum.

    Handles slab-shaped inputs (small K, e.g. the ``cap``-sized active-set
    training slab of DESIGN.md §11) as well as fleet-wide (N, P): a K that
    fits one block is taken whole (a full-dimension block needs no
    alignment), a larger K streams in ``block_k``-row blocks whose ragged
    tail is masked.  ``block_k`` must be a multiple of 16 and ``block_p`` of
    128 for the TPU (any value works in interpret mode)."""
    K, P = msgs.shape
    bk = K if K <= block_k else block_k
    bp = P if P <= block_p else block_p
    grid = (pl.cdiv(P, bp), pl.cdiv(K, bk))  # K innermost (reduction)
    out = pl.pallas_call(
        _make_kernel(K, bk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bk, bp), lambda p, k: (k, p)),
            pl.BlockSpec((bk, 1), lambda p, k: (k, 0)),
        ],
        out_specs=pl.BlockSpec((1, bp), lambda p, k: (0, p)),
        out_shape=jax.ShapeDtypeStruct((1, P), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, bp), jnp.float32)],
        interpret=interpret,
    )(msgs, weights.reshape(K, 1))
    return out[0]
