"""Jit'd public wrappers for the Pallas kernels.

Off the TPU (the CPU test path) the kernels execute in ``interpret=True``
mode — the kernel body runs in Python with real BlockSpec tiling semantics,
which is how they are validated against the ``ref.py`` oracles.  On a TPU
backend they always compile to Mosaic.
"""
from __future__ import annotations

import jax

from repro.kernels import ref
from repro.kernels.fedavg_reduce import fedavg_reduce as _fedavg_reduce
from repro.kernels.swa_attention import swa_attention as _swa_attention
from repro.kernels.ssd_scan import ssd_scan as _ssd_scan
from repro.kernels.vaoi_distance import vaoi_distance as _vaoi_distance


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def vaoi_distance(v, h, age, q, mu, **kw):
    kw.setdefault("interpret", _interpret())
    return _vaoi_distance(v, h, age, q, mu, **kw)


def fedavg_reduce(msgs, weights, **kw):
    """Weighted (K, P) -> (P,) reduce.  K may be the full client axis N or
    the compacted ``cap``-sized training slab (DESIGN.md §11); a K that
    fits one block is taken whole, so slab calls need no alignment."""
    kw.setdefault("interpret", _interpret())
    return _fedavg_reduce(msgs, weights, **kw)


def swa_attention(q, k, v, **kw):
    kw.setdefault("interpret", _interpret())
    return _swa_attention(q, k, v, **kw)


def ssd_scan(x, dt, A, Bm, Cm, **kw):
    kw.setdefault("interpret", _interpret())
    return _ssd_scan(x, dt, A, Bm, Cm, **kw)


__all__ = ["vaoi_distance", "fedavg_reduce", "swa_attention", "ssd_scan", "ref"]
