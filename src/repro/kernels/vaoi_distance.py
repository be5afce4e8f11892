"""Pallas TPU kernel: fused fleet-wide VAoI proxy evaluation.

Computes, for every client i in one HBM pass:
    M_i      = || v_i - h_i ||_2                      (Eq. 5)
    age_i'   = (age_i + [M_i >= mu]) * (1 - q_i)      (Eq. 7)

Tiling: grid (N/BN, F/BF).  The feature axis is reduced across the inner grid
dimension with a VMEM scratch accumulator; v/h tiles of (BN, BF) stream
through VMEM while the (BN, 1) age/q tiles stay resident.  Fusing distance +
threshold + age update avoids materializing the (N, F) diff and the (N,)
distance vector in HBM — at fleet scale (N ~ 1e5 clients, F = vocab-sized
features) the diff alone would be tens of GB of traffic.

The per-client vectors ride as (N, 1) columns: Mosaic refuses 1-D blocks
whose XLA layout it cannot match, and a column block only needs BN to be a
sublane multiple (or all of N).  Ragged edges are not padded in HBM: the
grid runs ``cdiv`` blocks, feature columns past F are masked to zero in the
kernel, and rows past N only reach the masked output store.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _make_kernel(mu: float, F: int, bf: int):
    def _kernel(v_ref, h_ref, age_ref, q_ref, m_ref, age_out_ref, acc_ref):
        j = pl.program_id(1)
        nf = pl.num_programs(1)

        @pl.when(j == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        diff = v_ref[...].astype(jnp.float32) - h_ref[...].astype(jnp.float32)
        sq = diff * diff
        if F % bf:  # ragged last F block: columns past F hold garbage
            cols = j * bf + jax.lax.broadcasted_iota(jnp.int32, sq.shape, 1)
            sq = jnp.where(cols < F, sq, 0.0)
        acc_ref[...] += jnp.sum(sq, axis=1, keepdims=True)

        @pl.when(j == nf - 1)
        def _finalize():
            m = jnp.sqrt(acc_ref[...])
            age = age_ref[...].astype(jnp.float32)
            q = q_ref[...].astype(jnp.float32)
            inc = jnp.where(m >= mu, age + 1.0, age)
            m_ref[...] = m
            age_out_ref[...] = inc * (1.0 - q)

    return _kernel


@functools.partial(jax.jit, static_argnames=("mu", "block_n", "block_f", "interpret"))
def vaoi_distance(
    v: jax.Array,
    h: jax.Array,
    age: jax.Array,
    q: jax.Array,
    mu: float,
    *,
    block_n: int = 128,
    block_f: int = 512,
    interpret: bool = False,
):
    """v, h: (N, F); age, q: (N,). Returns (m (N,), new_age (N,)) fp32.

    An axis that fits one block is taken whole; otherwise ``block_n`` must
    be a multiple of 16 and ``block_f`` of 128 for the TPU (any value works
    in interpret mode)."""
    N, F = v.shape
    bn = N if N <= block_n else block_n
    bf = F if F <= block_f else block_f
    col = pl.BlockSpec((bn, 1), lambda i, j: (i, 0))
    m, new_age = pl.pallas_call(
        _make_kernel(float(mu), F, bf),
        grid=(pl.cdiv(N, bn), pl.cdiv(F, bf)),
        in_specs=[
            pl.BlockSpec((bn, bf), lambda i, j: (i, j)),
            pl.BlockSpec((bn, bf), lambda i, j: (i, j)),
            col,
            col,
        ],
        out_specs=[col, col],
        out_shape=[
            jax.ShapeDtypeStruct((N, 1), jnp.float32),
            jax.ShapeDtypeStruct((N, 1), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bn, 1), jnp.float32)],
        interpret=interpret,
    )(v, h, age.reshape(N, 1), q.reshape(N, 1))
    return m[:, 0], new_age[:, 0]
