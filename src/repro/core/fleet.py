"""Client-sharded fleet simulator — Alg. 1 with the N axis sharded over the
mesh's ``data`` axis (DESIGN.md §9).

``run_simulation`` keeps every per-client array on one device; at fleet scale
``msg_params`` alone is N full model copies.  :func:`run_fleet` runs the SAME
``simulator.epoch_body`` under ``shard_map``: the global model and PRNG key
stay replicated, while ``msg_params``, ``h``, ``age``, ``battery``,
``pending``, ``counter``, ``retries``, ``backoff``, the client datasets, and
the per-client harvest, data-stream, and uplink-channel state live on their
shard of the fleet.  Only the
:class:`EpochOps` points differ from the solo path:

  * Alg. 2 selection — distributed top-k (``vaoi.select_topk_sharded``):
    local top-k per shard, all-gather the (score, index) candidate pairs,
    global top-k over candidates;
  * per-client training keys — this shard's slice of the global key split;
  * FedAvg — a ``psum`` of masked per-shard sums and counts
    (``kernels/fedavg_reduce`` as the per-shard reducer under
    ``use_kernel=True``); under active-set compaction (DESIGN.md §11) the
    per-shard sums come from each shard's local ``min(cap, N_loc)``
    training slab plus its old-carrier uploads;
  * metrics — ``psum`` scalar reductions.

Correctness contract (tested in ``tests/test_fleet.py``): for any N
divisible by the shard count, a fleet run matches the single-device
``run_simulation`` — integer slot dynamics (batteries, uploads, starts) and
VAoI ages exactly, float trajectories (f1, avg_m) to fp32 rounding.  The
exactness recipe is global-draw-and-slice: every random draw keeps its
single-device shape, computed from the replicated key on each shard, and the
shard slices its own window (see ``harvest.make_sharded_process``).
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import channel as channel_lib
from repro.core import harvest as harvest_lib
from repro.core import policies as policy_lib
from repro.data import stream as stream_lib
from repro.core.simulator import (
    Backend,
    EHFLConfig,
    EpochCarry,
    EpochOps,
    _compact_mean,
    _masked_mean,
    _masked_mean_kernel,
    drive_epochs,
    epoch_body,
    init_carry,
)

AXIS = "data"  # the client/fleet mesh axis


def fleet_ops(cfg: EHFLConfig, use_kernel: bool = False, axis_name: str = AXIS) -> EpochOps:
    """The distributed :class:`EpochOps`: selection, training keys, FedAvg,
    and metric reductions over a client-sharded fleet.  FedAvg is the SAME
    ``_masked_mean``/``_masked_mean_kernel`` as the solo path with a psum
    ``reduce_sum`` hook — masked per-shard sums and counts, psum'd."""
    N = cfg.num_clients
    psum = lambda x: jax.lax.psum(x, axis_name)
    agg = _masked_mean_kernel if use_kernel else _masked_mean

    def select(spec, age, t, k, key):
        return policy_lib.epoch_selection_sharded(
            spec, age, t, k, key, axis_name=axis_name, n_global=N
        )

    def train_keys(k_train, n_loc):
        return jax.lax.dynamic_slice_in_dim(
            jax.random.split(k_train, N), jax.lax.axis_index(axis_name) * n_loc, n_loc
        )

    return EpochOps(
        select=select,
        train_keys=train_keys,
        masked_mean=lambda contrib, mask, fb: agg(contrib, mask, fb, reduce_sum=psum),
        reduce_sum=lambda x: psum(jnp.sum(x)),
        # compaction is per-shard (each shard gathers its own starters into
        # a min(cap, N_loc) slab — DESIGN.md §11); aggregation stays a psum
        # of slab partial sums + old-carrier partial sums
        compact_mean=lambda slab, sm, old, om, fb: _compact_mean(
            slab, sm, old, om, fb, reduce_sum=psum, use_kernel=use_kernel
        ),
    )


def make_fleet_epoch_fn(
    cfg: EHFLConfig,
    backend: Backend,
    use_kernel: bool = False,
    axis_name: str = AXIS,
) -> Callable:
    """The ``shard_map``-interior counterpart of ``simulator.make_epoch_fn``:
    the same ``epoch_body`` with :func:`fleet_ops` and the sharded harvest
    process, as a pure ``(carry, t, images, labels) -> (carry, metrics)``
    over the LOCAL client shard."""
    spec = policy_lib.make_policy(
        cfg.policy, num_clients=cfg.num_clients, k=cfg.k, num_groups=cfg.num_groups
    )
    process = harvest_lib.make_sharded_process(
        cfg.harvest, p_bc=cfg.p_bc, axis_name=axis_name, n_global=cfg.num_clients,
        **dict(cfg.harvest_params),
    )
    stream_params = dict(cfg.stream_params)
    if cfg.stream in stream_lib.CLASS_CONDITIONED:
        # same backend-derived class count as the solo path (init_carry
        # builds the solo state the sharded step must be shape-compatible with)
        stream_params.setdefault("num_classes", backend.num_classes)
    stream = stream_lib.make_sharded_stream(
        cfg.stream, axis_name=axis_name, n_global=cfg.num_clients,
        **stream_params,
    )
    chan = channel_lib.make_sharded_channel(
        cfg.channel, axis_name=axis_name, n_global=cfg.num_clients,
        **dict(cfg.channel_params),
    )
    ops = fleet_ops(cfg, use_kernel, axis_name)
    return lambda carry, t, images, labels: epoch_body(
        carry, t, images, labels,
        cfg=cfg, backend=backend, spec=spec, process=process, ops=ops,
        stream=stream, channel=chan, use_kernel=use_kernel,
    )


def _carry_pspecs(cfg: EHFLConfig, carry_struct: EpochCarry) -> EpochCarry:
    """PartitionSpec tree for an :class:`EpochCarry`: client-axis leaves
    sharded over the fleet axis, global model + keys replicated (the
    scheduler-state rule of ``launch/sharding.py``)."""
    cl, rep = P(AXIS), P()
    hspec = None
    if carry_struct.harvest is not None:
        flags = harvest_lib.state_sharding_tree(cfg.harvest)
        hspec = jax.tree.map(lambda f: cl if f else rep, flags)
    sspec = None
    if carry_struct.stream is not None:
        sflags = stream_lib.state_sharding_tree(cfg.stream)
        sspec = jax.tree.map(lambda f: cl if f else rep, sflags)
    cspec = None
    if carry_struct.channel is not None:
        cflags = channel_lib.state_sharding_tree(cfg.channel)
        cspec = jax.tree.map(lambda f: cl if f else rep, cflags)
    return EpochCarry(
        global_params=jax.tree.map(lambda _: rep, carry_struct.global_params),
        msg_params=jax.tree.map(lambda _: cl, carry_struct.msg_params),
        h=cl, age=cl, battery=cl, pending=cl, counter=cl, key=rep,
        harvest=hspec,
        stream=sspec,
        retries=cl, backoff=cl,
        channel=cspec,
    )


def fleet_program(
    cfg: EHFLConfig,
    backend: Backend,
    data: Dict[str, jax.Array],
    *,
    mesh: Mesh | None = None,
    use_kernel: bool = False,
) -> Tuple[EpochCarry, Callable, Dict[str, jax.Array], Mesh]:
    """Build the sharded fleet program: the initial carry (born sharded —
    ``init_carry`` is jitted with sharded out_shardings, so the N model
    copies of ``msg_params`` never materialize on one device), the jitted
    ``scan_chunk(carry, ts, images, labels)``, the sharded client data, and
    the mesh.  ``run_fleet`` drives it; ``benchmarks/fleet_bench`` times it.
    """
    if mesh is None:
        # core->launch is a deliberate lazy import: mesh construction lives
        # with the other launch-layer topology code (DESIGN.md §1)
        from repro.launch.mesh import make_fleet_mesh

        mesh = make_fleet_mesh(num_clients=cfg.num_clients)
    if AXIS not in mesh.axis_names:
        raise ValueError(f"fleet mesh needs a {AXIS!r} axis; got {mesh.axis_names}")
    shards = mesh.shape[AXIS]
    if cfg.num_clients % shards:
        raise ValueError(
            f"num_clients={cfg.num_clients} must divide over {shards} shards"
        )

    epoch_fn = make_fleet_epoch_fn(cfg, backend, use_kernel=use_kernel)
    carry_struct = jax.eval_shape(lambda: init_carry(cfg, backend))
    specs = _carry_pspecs(cfg, carry_struct)
    cl, rep = P(AXIS), P()

    # PartitionSpec is a tuple subclass: an explicit is_leaf keeps tree.map
    # from descending into the specs themselves
    carry_shardings = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), specs, is_leaf=lambda x: isinstance(x, P)
    )
    carry0 = jax.jit(
        lambda: init_carry(cfg, backend), out_shardings=carry_shardings
    )()

    # the carry is donated (its msg_params shard is still N_loc model
    # copies per device); the data/ts args are reused across eval_every
    # chunks, so they are deliberately NOT donated
    scan_chunk = jax.jit(
        jax.shard_map(
            lambda c, ts, images, labels: jax.lax.scan(
                lambda cc, t: epoch_fn(cc, t, images, labels), c, ts
            ),
            mesh=mesh,
            in_specs=(specs, rep, cl, cl),
            out_specs=(specs, rep),
            check_vma=False,
        ),
        donate_argnums=(0,),
    )

    cl_sharding = NamedSharding(mesh, cl)
    sharded_data = {
        "images": jax.device_put(data["images"], cl_sharding),
        "labels": jax.device_put(data["labels"], cl_sharding),
    }
    return carry0, scan_chunk, sharded_data, mesh


def run_fleet(
    cfg: EHFLConfig,
    backend: Backend,
    data: Dict[str, jax.Array],
    *,
    mesh: Mesh | None = None,
    use_kernel: bool = False,
) -> Dict[str, Any]:
    """Run T epochs of Alg. 1 with the client axis sharded over the mesh.
    Same return contract as ``run_simulation`` (metric trajectories + final
    model + carry), plus ``num_shards``."""
    carry, scan_chunk, sharded_data, mesh = fleet_program(
        cfg, backend, data, mesh=mesh, use_kernel=use_kernel
    )
    out = drive_epochs(
        lambda c, ts: scan_chunk(c, ts, sharded_data["images"], sharded_data["labels"]),
        carry, cfg, backend, data,
    )
    out["num_shards"] = mesh.shape[AXIS]
    return out
