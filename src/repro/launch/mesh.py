"""Production mesh definitions (TPU v5e target).

Single pod:  (16, 16)    -> axes ("data", "model")   = 256 chips
Multi-pod:   (2, 16, 16) -> axes ("pod", "data", "model") = 512 chips

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run sets XLA_FLAGS before any jax import; tests see 1 CPU).

Every mesh here uses ``Auto`` axes: the sharding rules of
``launch/sharding.py`` are GSPMD hints, not sharding-in-types.  Under
``jax.make_mesh``'s default ``Explicit`` axes the embedding gather of a
batch-sharded token array against the FSDP table (``V->model, d->data``)
would need ``data`` on two output dims and is refused.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes, devices=None):
    kw = {} if devices is None else {"devices": devices}
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes), **kw)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_host_mesh(model_parallel: int = 1):
    """Tiny mesh over whatever devices exist (tests / examples on CPU)."""
    n = len(jax.devices())
    dp = n // model_parallel
    return _mesh((dp, model_parallel), ("data", "model"))


def make_fleet_mesh(num_shards: int | None = None, *, num_clients: int | None = None):
    """1-D client-fleet mesh, axes ("data",) — what ``core/fleet.run_fleet``
    shards the N axis over (DESIGN.md §9).  Defaults to every visible device
    (use ``XLA_FLAGS=--xla_force_host_platform_device_count=K`` to virtualize
    K CPU devices).  If ``num_clients`` is given, the shard count is clamped
    to its largest divisor so the fleet divides evenly."""
    n = num_shards or len(jax.devices())
    if num_clients is not None:
        n = min(n, num_clients)
        while num_clients % n:
            n -= 1
    return _mesh((n,), ("data",))


def data_axes(mesh) -> tuple:
    """The batch-sharding axes for this mesh ((pod, data) when multi-pod)."""
    names = mesh.axis_names
    return ("pod", "data") if "pod" in names else ("data",)


# Hardware constants for the roofline model (TPU v5e)
PEAK_FLOPS_BF16 = 197e12  # per chip
HBM_BW = 819e9  # bytes/s per chip
ICI_BW = 50e9  # bytes/s per link
