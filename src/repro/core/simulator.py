"""Alg. 1 — the full EHFL loop, as a single jitted program.

TPU-native formulation (see DESIGN.md §3): all per-client state is stacked on
a leading N axis (batteries, ages, pending flags, feature moments, *and model
parameters*); epochs are a ``lax.scan``; the slot-level energy dynamics are an
inner scan of cheap integer ops (``repro.core.energy``); local training is a
vmapped ``kappa``-step SGD scan over the *active set only* — the started
clients are gathered into a static ``PolicySpec.max_active``-sized slab, so
per-epoch training FLOPs scale with the participating set, not the
population (active-set compaction, DESIGN.md §11; ``compact=False`` forces
the dense all-N path).  The client axis is what shards over the
``data`` mesh axis at scale — ``repro.core.fleet.run_fleet`` runs this same
epoch body client-sharded under ``shard_map`` (DESIGN.md §9).

The epoch body is exposed as a pure ``(carry, t) -> (carry, metrics)``
function via :func:`make_epoch_fn`, which is what makes :func:`run_batch`
possible: the whole epoch scan (eval included) ``vmap``s over a seed axis and
runs a full multi-seed sweep cell as ONE jitted call (DESIGN.md §8).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Callable, Dict, NamedTuple, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core import channel as channel_lib
from repro.core import energy as energy_lib
from repro.core import harvest as harvest_lib
from repro.core import policies as policy_lib
from repro.core import vaoi as vaoi_lib
from repro.data import stream as stream_lib
from repro.optim import sgd_update

# The VAoI probe forward (Eq. 5) runs this many clients per step of a loop
# over the client axis.  One conv batch of all N * probe_size images (2000 at
# the paper's N=100, probe 20, with the 32 px CNN) overflows the stack of the
# TPU compiler's conv-fusion cost model (libtpu 0.0.34) and kills the process
# at compile time; 10 clients per step compiles at every paper-width shape.
PROBE_CLIENTS_PER_STEP = 10


@dataclass(frozen=True)
class EHFLConfig:
    num_clients: int = 100
    epochs: int = 500
    slots_per_epoch: int = 30  # S
    kappa: int = 20  # training cost in slots == battery units
    p_bc: float = 0.1  # mean harvest rate (Bernoulli probability, Eq. 3)
    k: int = 10  # selection budget (Alg. 2)
    mu: float = 0.5  # VAoI significance threshold
    lr: float = 0.01  # SGD gamma
    probe_size: int = 30  # |B_i| for the proxy forward pass
    e_max: int = 25  # kappa + 5
    policy: str = "vaoi"
    num_groups: int = 0  # FedBacys group count G (0 = default N // k)
    alpha: float = 0.1  # Dirichlet concentration (data partition)
    seed: int = 0
    eval_every: int = 10
    aux_note: str = ""
    # harvest scenario (repro.core.harvest; "bernoulli" keeps p_bc semantics
    # and reproduces seed behavior exactly).  harvest_params is a tuple of
    # (name, value) pairs so the config stays frozen/hashable.
    harvest: str = "bernoulli"
    harvest_params: Tuple[Tuple[str, float], ...] = ()
    # streaming-data scenario (repro.data.stream; "static" is the frozen
    # Dirichlet partition and reproduces seed behavior exactly).  Same
    # (name, value) tuple convention as harvest_params.
    stream: str = "static"
    stream_params: Tuple[Tuple[str, float], ...] = ()
    # uplink channel scenario (repro.core.channel; "ideal" is the lossless
    # pre-channel behavior and reproduces it exactly).  Same (name, value)
    # tuple convention as harvest_params/stream_params.
    channel: str = "ideal"
    channel_params: Tuple[Tuple[str, float], ...] = ()
    # retry state machine for failed uploads (DESIGN.md §12): a failed
    # carrier re-queues with capped exponential backoff (skip
    # min(2^(attempts-1), backoff_cap) epochs before re-contending) and is
    # dropped outright after max_retries failures — the spent energy is
    # never refunded.
    max_retries: int = 3
    backoff_cap: int = 8
    # active-set compaction (DESIGN.md §11): train only the clients that
    # actually started this epoch, gathered into a static-size slab of
    # ``PolicySpec.max_active`` lanes.  "auto" (the default) compacts
    # whenever the policy's slab is smaller than N (fedavg therefore always
    # falls back to the dense path); False forces the dense path.
    compact: Any = "auto"  # bool | "auto"

    def harvest_process(self) -> harvest_lib.HarvestProcess:
        return harvest_lib.make_process(
            self.harvest, p_bc=self.p_bc, **dict(self.harvest_params)
        )

    def data_stream(self, num_classes: int | None = None) -> stream_lib.DataStream:
        """``num_classes`` is the dataset's class count (the simulator passes
        ``backend.num_classes``); an explicit ``stream_params`` entry wins."""
        params = dict(self.stream_params)
        if num_classes is not None and self.stream in stream_lib.CLASS_CONDITIONED:
            params.setdefault("num_classes", num_classes)
        return stream_lib.make_stream(self.stream, **params)

    def channel_process(self) -> channel_lib.ChannelProcess:
        return channel_lib.make_channel(self.channel, **dict(self.channel_params))


class Backend(NamedTuple):
    """Model plug-in for the simulator (CNN for the paper; LMs at scale)."""

    init: Callable[[jax.Array], Any]
    grad_loss: Callable[[Any, jax.Array, jax.Array], Tuple[jax.Array, Any]]
    feature: Callable[[Any, jax.Array], jax.Array]  # (params, inputs) -> (F,)
    predict: Callable[[Any, jax.Array], jax.Array]
    feature_dim: int
    num_classes: int


class EpochCarry(NamedTuple):
    global_params: Any
    msg_params: Any  # (N, ...) stacked messages
    h: jax.Array  # (N, F) historical moments
    age: jax.Array  # (N,)
    battery: jax.Array  # (N,)
    pending: jax.Array  # (N,) bool
    counter: jax.Array  # (N,)
    key: jax.Array
    # persistent HarvestProcess state (None for per-epoch-reseeded processes
    # such as the memoryless bernoulli default — see DESIGN.md §7)
    harvest: Any = None
    # persistent DataStream state (None for the stateless "static" stream —
    # see DESIGN.md §10)
    stream: Any = None
    # lossy-uplink retry state machine (DESIGN.md §12): per-client count of
    # failed delivery attempts for the CURRENT pending message, and epochs
    # left to sit out before re-contending (capped exponential backoff).
    # Both stay all-zero under the "ideal" channel.
    retries: Any = None  # (N,) int32
    backoff: Any = None  # (N,) int32
    # persistent ChannelProcess state (None for the stateless "ideal"
    # default — see DESIGN.md §12)
    channel: Any = None


def _local_train(
    params: Any,
    images: jax.Array,
    labels: jax.Array,
    key: jax.Array,
    cfg: EHFLConfig,
    backend: Backend,
    with_feature: bool = True,
) -> Tuple[Any, jax.Array | None]:
    """BATCHTRAIN (Alg. 1 lines 23-29): kappa minibatch SGD steps over one
    permutation pass; accumulates the Eq. (6) historical moment.

    ``with_feature=False`` drops the per-step feature forward pass and
    returns ``None`` for the moment — the Eq. 6 accumulator only exists for
    VAoI policies, and ``backend.feature`` is a pure function of the params,
    so skipping it leaves the SGD trajectory bit-identical."""
    n = images.shape[0]
    bs = max(1, n // cfg.kappa)
    perm = jax.random.permutation(key, n)[: cfg.kappa * bs].reshape(cfg.kappa, bs)

    def step(carry, idx):
        params, fsum = carry
        imgs, lbls = images[idx], labels[idx]
        _, grads = backend.grad_loss(params, imgs, lbls)
        params = sgd_update(params, grads, cfg.lr)
        if with_feature:
            f = backend.feature(params, imgs)  # batch-mean feature of w^(t,b+1)
            fsum = fsum + f * bs
        return (params, fsum), None

    fsum0 = jnp.zeros((backend.feature_dim,), jnp.float32) if with_feature else None
    (params, fsum), _ = jax.lax.scan(step, (params, fsum0), perm)
    return params, fsum / (cfg.kappa * bs) if with_feature else None


def _masked_mean(
    stacked: Any, mask: jax.Array, fallback: Any, reduce_sum: Callable | None = None
) -> Any:
    """FedAvg over the masked clients; fallback when no uploads.
    ``reduce_sum`` folds per-shard partial sums/counts into fleet totals
    (the fleet path passes a psum; default identity = full client axis)."""
    r = reduce_sum or (lambda x: x)
    cnt = r(jnp.sum(mask.astype(jnp.float32)))

    def agg(leaf, fb):
        m = mask.reshape((-1,) + (1,) * (leaf.ndim - 1)).astype(leaf.dtype)
        s = r(jnp.sum(leaf * m, axis=0)) / jnp.maximum(cnt, 1.0).astype(leaf.dtype)
        return jnp.where(cnt > 0, s, fb)

    return jax.tree.map(agg, stacked, fallback)


def flatten_clients(stacked: Any) -> Tuple[jax.Array, Any]:
    """Ravel a stacked (N, ...) pytree into one (N, P) matrix + structure aux
    (the layout the ``fedavg_reduce`` Pallas kernel consumes)."""
    leaves, treedef = jax.tree_util.tree_flatten(stacked)
    flat = jnp.concatenate([l.reshape((l.shape[0], -1)) for l in leaves], axis=1)
    return flat, (treedef, [(l.shape[1:], l.dtype) for l in leaves])


def unflatten_clients(vec: jax.Array, aux: Any) -> Any:
    """Inverse of :func:`flatten_clients` for one aggregated (P,) vector."""
    treedef, shapes = aux
    out, i = [], 0
    for shape, dtype in shapes:
        size = 1
        for d in shape:
            size *= d
        out.append(vec[i : i + size].reshape(shape).astype(dtype))
        i += size
    return jax.tree_util.tree_unflatten(treedef, out)


def _masked_mean_kernel(
    stacked: Any, mask: jax.Array, fallback: Any, reduce_sum: Callable | None = None
) -> Any:
    """:func:`_masked_mean` through the ``kernels/fedavg_reduce`` Pallas
    kernel: flatten the contrib pytree to (N, P), weighted-reduce with
    normalized mask weights, unflatten (DESIGN.md §4).  Same ``reduce_sum``
    hook as :func:`_masked_mean` (the fleet path reduces per shard and
    psums the (P,) partials)."""
    from repro.kernels import ops as kops

    r = reduce_sum or (lambda x: x)
    cnt = r(jnp.sum(mask.astype(jnp.float32)))
    w = mask.astype(jnp.float32) / jnp.maximum(cnt, 1.0)
    flat, aux = flatten_clients(stacked)
    mean = unflatten_clients(r(kops.fedavg_reduce(flat, w)), aux)
    return jax.tree.map(lambda s, fb: jnp.where(cnt > 0, s, fb), mean, fallback)


def _compact_mean(
    slab: Any,
    slab_mask: jax.Array,
    old: Any,
    old_mask: jax.Array,
    fallback: Any,
    reduce_sum: Callable | None = None,
    use_kernel: bool = False,
) -> Any:
    """FedAvg for the compacted path (DESIGN.md §11): this epoch's fresh
    uploads live in the ``(cap, ...)`` training slab (``slab_mask``), while
    ``pending_in`` carriers upload their OLD message straight from the
    N-wide ``old`` tree (``old_mask``) — their stale params were never
    re-trained, so there is nothing to gather.  The two partial sums share
    one count; the old-carrier pass is bandwidth-only (no training FLOPs).
    ``reduce_sum`` folds per-shard partials into fleet totals, exactly as in
    :func:`_masked_mean`."""
    r = reduce_sum or (lambda x: x)
    cnt = r(
        jnp.sum(slab_mask.astype(jnp.float32)) + jnp.sum(old_mask.astype(jnp.float32))
    )
    if use_kernel:
        from repro.kernels import ops as kops

        sflat, aux = flatten_clients(slab)
        oflat, _ = flatten_clients(old)
        tot = r(
            kops.fedavg_reduce(sflat, slab_mask.astype(jnp.float32))
            + kops.fedavg_reduce(oflat, old_mask.astype(jnp.float32))
        )
        mean = unflatten_clients(tot / jnp.maximum(cnt, 1.0), aux)
        return jax.tree.map(lambda s, fb: jnp.where(cnt > 0, s, fb), mean, fallback)

    def agg(s_leaf, o_leaf, fb):
        ms = slab_mask.reshape((-1,) + (1,) * (s_leaf.ndim - 1)).astype(s_leaf.dtype)
        mo = old_mask.reshape((-1,) + (1,) * (o_leaf.ndim - 1)).astype(o_leaf.dtype)
        tot = r(jnp.sum(s_leaf * ms, axis=0) + jnp.sum(o_leaf * mo, axis=0))
        s = tot / jnp.maximum(cnt, 1.0).astype(s_leaf.dtype)
        return jnp.where(cnt > 0, s, fb)

    return jax.tree.map(agg, slab, old, fallback)


def resolve_compact_cap(cfg: EHFLConfig, spec: policy_lib.PolicySpec) -> int | None:
    """The static training-slab size for this (config, policy), or ``None``
    for the dense path.  Compaction engages when the policy's per-epoch
    starter bound (``PolicySpec.max_active``) is below N — ``fedavg``
    (max_active == N) therefore always falls back dense, under "auto" AND
    under ``compact=True`` (the slab would be the whole fleet)."""
    # identity checks: `0 in (True, False, "auto")` is True (0 == False), so
    # a membership test would let falsy non-bool values slip into compaction
    if cfg.compact is False:
        return None
    if cfg.compact is not True and cfg.compact != "auto":
        raise ValueError(f"compact must be True, False or 'auto'; got {cfg.compact!r}")
    cap = spec.max_active
    if cap <= 0 or cap >= cfg.num_clients:
        return None
    return cap


def init_carry(cfg: EHFLConfig, backend: Backend, seed: jax.Array | int | None = None) -> EpochCarry:
    """Initial :class:`EpochCarry` for one simulation.  ``seed`` defaults to
    ``cfg.seed`` and may be a traced scalar (so this vmaps over seeds)."""
    N = cfg.num_clients
    key = jax.random.PRNGKey(cfg.seed if seed is None else seed)
    k_init, k_run = jax.random.split(key)
    global_params = backend.init(k_init)
    msg_params = jax.tree.map(lambda x: jnp.broadcast_to(x[None], (N,) + x.shape), global_params)
    process = cfg.harvest_process()
    hstate = None
    if process.persistent:
        k_run, k_harvest = jax.random.split(k_run)
        hstate = process.init(k_harvest, N)
    # stream state is split AFTER harvest state, so existing harvest-scenario
    # PRNG chains are unchanged; the stateless "static" default splits
    # nothing, keeping the seed chain bit-identical (DESIGN.md §10)
    data_stream = cfg.data_stream(backend.num_classes)
    sstate = None
    if data_stream.persistent:
        k_run, k_stream = jax.random.split(k_run)
        sstate = data_stream.init(k_stream, N)
    # channel state splits AFTER stream state (same chain-preservation rule:
    # the stateless "ideal" default splits nothing, so harvest/stream PRNG
    # chains — and the whole default trajectory — stay bit-identical)
    chan = cfg.channel_process()
    cstate = None
    if chan.persistent:
        k_run, k_chan = jax.random.split(k_run)
        cstate = chan.init(k_chan, N)
    return EpochCarry(
        global_params=global_params,
        msg_params=msg_params,
        h=jnp.zeros((N, backend.feature_dim), jnp.float32),
        age=jnp.zeros((N,), jnp.float32),
        battery=jnp.zeros((N,), jnp.int32),
        pending=jnp.zeros((N,), bool),
        counter=jnp.zeros((N,), jnp.int32),
        key=k_run,
        harvest=hstate,
        stream=sstate,
        retries=jnp.zeros((N,), jnp.int32),
        backoff=jnp.zeros((N,), jnp.int32),
        channel=cstate,
    )


class EpochOps(NamedTuple):
    """The five shard-aware points of the epoch body.  The solo defaults
    below operate on the full client axis; ``core/fleet.py`` substitutes
    distributed forms (psum/all-gather) so one :func:`epoch_body` serves
    both the single-device and the client-sharded path (DESIGN.md §9)."""

    select: Callable  # (spec, age, t, k, key) -> (N_loc,) mask
    train_keys: Callable  # (k_train, n_loc) -> (n_loc, 2) per-client keys
    masked_mean: Callable  # (contrib, mask, fallback) -> aggregated params
    reduce_sum: Callable  # (N_loc,) -> fleet-wide scalar
    # compacted FedAvg (DESIGN.md §11):
    # (slab, slab_mask, old, old_mask, fallback) -> aggregated params
    compact_mean: Callable = _compact_mean


def solo_ops(cfg: EHFLConfig, use_kernel: bool = False) -> EpochOps:
    return EpochOps(
        select=policy_lib.epoch_selection,
        train_keys=lambda k_train, n_loc: jax.random.split(k_train, cfg.num_clients),
        masked_mean=_masked_mean_kernel if use_kernel else _masked_mean,
        reduce_sum=jnp.sum,
        compact_mean=lambda slab, sm, old, om, fb: _compact_mean(
            slab, sm, old, om, fb, use_kernel=use_kernel
        ),
    )


def epoch_body(
    carry: EpochCarry,
    t: jax.Array,
    images: jax.Array,
    labels: jax.Array,
    *,
    cfg: EHFLConfig,
    backend: Backend,
    spec: policy_lib.PolicySpec,
    process: harvest_lib.HarvestProcess,
    ops: EpochOps,
    stream: stream_lib.DataStream | None = None,
    channel: channel_lib.ChannelProcess | None = None,
    use_kernel: bool = False,
) -> Tuple[EpochCarry, Dict[str, jax.Array]]:
    """One epoch of Alg. 1 over the clients in ``carry`` (all N, or one
    shard's slice when driven by ``core/fleet.py`` — ``ops`` carries the
    only four operations that differ).  ``images``/``labels`` are the
    per-client sample POOLS; ``stream`` turns them into this epoch's view
    (DESIGN.md §10; ``None`` and the "static" stream are the identity).
    ``channel`` decides which uploads actually land (DESIGN.md §12; ``None``
    and the "ideal" channel deliver everything, bit-identically)."""
    N, S, kappa = cfg.num_clients, cfg.slots_per_epoch, cfg.kappa
    n_loc = carry.age.shape[0]
    k_sel, k_scan, k_train, k_next = jax.random.split(carry.key, 4)

    # --- per-epoch data view (DataStream, DESIGN.md §10) ---
    stream_state = carry.stream
    if stream is not None:
        idx, stream_state = stream.step(stream_state, t, labels)
        images, labels = stream_lib.apply_view(idx, images, labels)
    probe_imgs = images[:, : cfg.probe_size]

    # --- CLIENTSELECT (Alg. 2) on the freshly-broadcast global model ---
    selected = ops.select(spec, carry.age, t, cfg.k, k_sel)
    if spec.uses_vaoi:
        v = jax.lax.map(
            lambda imgs: backend.feature(carry.global_params, imgs),
            probe_imgs, batch_size=min(PROBE_CLIENTS_PER_STEP, n_loc),
        )
        if use_kernel:  # fused Pallas kernel (Eq. 5 + Eq. 7 in one pass)
            from repro.kernels import ops as kops

            m, age = kops.vaoi_distance(
                v, carry.h, carry.age, selected.astype(jnp.float32), cfg.mu
            )
        else:
            m = vaoi_lib.feature_distance(v, carry.h)
            age = vaoi_lib.vaoi_update(carry.age, m, selected.astype(jnp.float32), cfg.mu)
    else:
        age = carry.age
        m = jnp.zeros((n_loc,), jnp.float32)

    # --- slot-level energy dynamics ---
    want_fn = policy_lib.make_want_fn(spec, selected, S, kappa)
    opp_fn = policy_lib.make_opportunity_fn(spec, selected, S, kappa)
    st0 = energy_lib.SlotState(
        battery=carry.battery,
        started=jnp.zeros((n_loc,), bool),
        start_slot=jnp.full((n_loc,), S, jnp.int32),
        pending=carry.pending,
        uploaded=jnp.zeros((n_loc,), bool),
        counter=carry.counter,
        energy_used=jnp.zeros((n_loc,), jnp.int32),
        key=k_scan,
        harvest=carry.harvest,  # None -> re-seeded from k_scan in scan_epoch
        stream=stream_state,  # rides the slot scan untouched (hook for
        # slot-granular arrival processes; per-epoch streams step above)
    )
    st = energy_lib.scan_epoch(
        st0, S=S, kappa=kappa, e_max=cfg.e_max, process=process,
        want_fn=want_fn, count_opportunity_fn=opp_fn,
        # retry backoff gates transmission for the whole epoch (the pending
        # message — and its energy — is held, not re-contended)
        tx_allowed=(carry.backoff == 0) if channel is not None else None,
    )

    # --- uplink channel + retry state machine (DESIGN.md §12) ---
    # ``st.uploaded`` clients SPENT a transmission unit; the channel decides
    # whose message landed.  A failed carrier re-queues (pending again, an
    # old-carrier retransmission once its backoff expires), re-ages its VAoI
    # by one version per failure, and is dropped after max_retries — the
    # energy is never refunded.
    upload_mask = st.uploaded
    pending_out, retries_out, backoff_out, cstate_out = (
        st.pending, carry.retries, carry.backoff, None
    )
    failed = dropped = None
    if channel is not None:
        delivered, cstate_out = channel.step(carry.channel, st.uploaded)
        failed = st.uploaded & ~delivered
        attempts = carry.retries + failed.astype(jnp.int32)
        dropped = failed & (attempts >= cfg.max_retries)
        retrying = failed & ~dropped
        # capped exponential backoff: sit out min(2^(attempts-1), cap)
        # epochs before re-contending (attempt counts are tiny, but the
        # shift is clamped so a misconfigured max_retries can't overflow)
        boff = jnp.minimum(
            jnp.left_shift(1, jnp.minimum(attempts - 1, 30)), cfg.backoff_cap
        ).astype(jnp.int32)
        upload_mask = delivered
        pending_out = st.pending | retrying
        retries_out = jnp.where(
            delivered | dropped, 0, jnp.where(retrying, attempts, carry.retries)
        )
        backoff_out = jnp.where(retrying, boff, jnp.maximum(carry.backoff - 1, 0))
        # VAoI re-aging: the scheduler must see the server's TRUE staleness —
        # a lost version is one more version the server is behind by
        age = age + failed.astype(age.dtype)
        if not channel.persistent:
            cstate_out = None

    # --- local training (only VAoI policies read the Eq. 6 moment h) ---
    pending_in = carry.pending  # entered the epoch with an unsent (old) message?
    train_keys = ops.train_keys(k_train, n_loc)
    cap = resolve_compact_cap(cfg, spec)
    train_one = lambda imgs, lbls, k: _local_train(
        carry.global_params, imgs, lbls, k, cfg, backend, with_feature=spec.uses_vaoi
    )

    if cap is None:
        # --- dense path: vmap over all clients, mask by st.started ---
        trained, h_new = jax.vmap(train_one)(images, labels, train_keys)
        started_m = st.started
        sel = lambda new, old: jax.tree.map(
            lambda a, b: jnp.where(started_m.reshape((-1,) + (1,) * (a.ndim - 1)), a, b), new, old
        )
        msg_params = sel(trained, carry.msg_params)
        h = jnp.where(started_m[:, None], h_new, carry.h) if spec.uses_vaoi else carry.h

        # aggregation (DELIVERED uploads of this epoch; old-pending uploads
        # use old msgs — a lossy channel shrinks the mask, never the msgs)
        contrib = jax.tree.map(
            lambda old, new: jnp.where(
                pending_in.reshape((-1,) + (1,) * (old.ndim - 1)), old, new
            ),
            carry.msg_params,
            msg_params,
        )
        new_global = ops.masked_mean(contrib, upload_mask, carry.global_params)
    else:
        # --- active-set compaction (DESIGN.md §11): gather the started
        # clients into a static (cap_loc, ...) slab, train only the slab,
        # scatter params/moments back.  Starters never exceed the slab —
        # they are a subset of the selection mask, whose popcount
        # ``PolicySpec.max_active`` bounds (asserted in tests/test_compact).
        cap_loc = min(cap, n_loc)
        # stable argsort of the ~started mask: started clients first, in
        # ascending client order — so slab lane j is the j-th started client
        slab_idx = jnp.argsort(~st.started)[:cap_loc]
        slab_valid = jnp.arange(cap_loc) < jnp.sum(st.started.astype(jnp.int32))
        trained, h_slab = jax.vmap(train_one)(
            images[slab_idx], labels[slab_idx], train_keys[slab_idx]
        )
        # invalid (padding) lanes scatter out of bounds -> dropped
        scat_idx = jnp.where(slab_valid, slab_idx, n_loc)
        msg_params = jax.tree.map(
            lambda mp, tr: mp.at[scat_idx].set(tr, mode="drop"), carry.msg_params, trained
        )
        h = (
            carry.h.at[scat_idx].set(h_slab, mode="drop")
            if spec.uses_vaoi
            else carry.h
        )

        # aggregation: fresh DELIVERED uploads (delivered & ~pending_in, a
        # subset of started) reduce over the slab; pending_in carriers upload
        # their OLD message from the N-wide msg tree (bandwidth-only pass).
        # The channel's delivery mask gates both passes identically to the
        # dense path, so lossy compact == lossy dense stays exact.
        slab_new = (upload_mask & ~pending_in)[slab_idx] & slab_valid
        old_mask = upload_mask & pending_in
        new_global = ops.compact_mean(
            trained, slab_new, carry.msg_params, old_mask, carry.global_params
        )

    zero = jnp.zeros((), jnp.int32)
    metrics = {
        "energy": ops.reduce_sum(st.energy_used),
        "avg_age": ops.reduce_sum(age) / N,
        "n_started": ops.reduce_sum(st.started.astype(jnp.int32)),
        "n_uploaded": ops.reduce_sum(st.uploaded.astype(jnp.int32)),
        "avg_m": ops.reduce_sum(m) / N,
        # channel outcomes: n_uploaded counts ATTEMPTS (energy spent);
        # n_delivered what landed; n_failed/n_dropped the channel's toll
        "n_delivered": ops.reduce_sum(upload_mask.astype(jnp.int32)),
        "n_failed": ops.reduce_sum(failed.astype(jnp.int32)) if failed is not None else zero,
        "n_dropped": ops.reduce_sum(dropped.astype(jnp.int32)) if dropped is not None else zero,
    }
    return (
        EpochCarry(
            global_params=new_global,
            msg_params=msg_params,
            h=h,
            age=age,
            battery=st.battery,
            pending=pending_out,
            counter=st.counter,
            key=k_next,
            harvest=st.harvest if process.persistent else None,
            stream=st.stream if stream is not None and stream.persistent else None,
            retries=retries_out,
            backoff=backoff_out,
            channel=cstate_out,
        ),
        metrics,
    )


def make_epoch_fn(
    cfg: EHFLConfig,
    backend: Backend,
    data: Dict[str, jax.Array],
    use_kernel: bool = False,
) -> Callable[[EpochCarry, jax.Array], Tuple[EpochCarry, Dict[str, jax.Array]]]:
    """One epoch of Alg. 1 as a pure ``(carry, t) -> (carry, metrics)``
    function — scan it for a solo run, vmap the scan for a seed sweep."""
    spec = policy_lib.make_policy(
        cfg.policy, num_clients=cfg.num_clients, k=cfg.k, num_groups=cfg.num_groups
    )
    process = cfg.harvest_process()
    stream = cfg.data_stream(backend.num_classes)
    chan = cfg.channel_process()
    ops = solo_ops(cfg, use_kernel)
    return lambda carry, t: epoch_body(
        carry, t, data["images"], data["labels"],
        cfg=cfg, backend=backend, spec=spec, process=process, ops=ops,
        stream=stream, channel=chan, use_kernel=use_kernel,
    )


@functools.lru_cache(maxsize=16)
def _jitted_predict(predict: Callable) -> Callable:
    """Per-``backend.predict`` jit cache: ``drive_epochs`` used to build a
    fresh ``jax.jit(lambda ...)`` wrapper per call, so every simulation
    re-traced eval; keying on the predict callable reuses the trace across
    runs (and across the eval_every chunks of one run) for a long-lived
    backend.  Bounded so freshly-built backends (each ``cnn_backend`` call
    makes a new predict closure) evict instead of pinning their closures
    and compiled executables forever."""
    return jax.jit(predict)


def drive_epochs(
    scan_chunk: Callable,
    carry: EpochCarry,
    cfg: EHFLConfig,
    backend: Backend,
    data: Dict[str, jax.Array],
) -> Dict[str, Any]:
    """The host loop shared by :func:`run_simulation` and ``fleet.run_fleet``:
    scan epochs in ``eval_every`` chunks with periodic macro-F1 eval.
    ``scan_chunk(carry, ts) -> (carry, metrics)`` hides solo vs sharded.

    ``scan_chunk`` may donate its carry argument (both callers do): the
    loop never reuses a carry after passing it in."""
    all_metrics = []
    f1s, f1_epochs = [], []
    eval_fn = _jitted_predict(backend.predict)
    from repro.models.cnn import macro_f1

    chunk = max(1, cfg.eval_every)
    t = 0
    while t < cfg.epochs:
        n = min(chunk, cfg.epochs - t)
        carry, ms = scan_chunk(carry, jnp.arange(t, t + n))
        all_metrics.append(ms)
        preds = eval_fn(carry.global_params, data["test_images"])
        f1s.append(float(macro_f1(preds, data["test_labels"], backend.num_classes)))
        f1_epochs.append(t + n)
        t += n

    metrics = {k: jnp.concatenate([m[k] for m in all_metrics]) for k in all_metrics[0]}
    metrics["f1"] = jnp.array(f1s)
    metrics["f1_epochs"] = jnp.array(f1_epochs)
    metrics["total_energy"] = jnp.sum(metrics["energy"])
    return {"metrics": metrics, "global_params": carry.global_params, "carry": carry}


def run_simulation(
    cfg: EHFLConfig,
    backend: Backend,
    data: Dict[str, jax.Array],
    use_kernel: bool = False,
) -> Dict[str, Any]:
    """Run T epochs of Alg. 1. Returns metric trajectories + final model."""

    # the client data enters as jit ARGUMENTS: a closed-over array is baked
    # into the program as a constant, which at paper width (~370 MB of
    # client images) swamps compilation.  The carry is donated:
    # msg_params is N stacked model copies, and without donation every
    # eval_every chunk allocates a fresh copy.
    def chunk(c, ts, images, labels):
        epoch_fn = make_epoch_fn(
            cfg, backend, {"images": images, "labels": labels}, use_kernel=use_kernel
        )
        return jax.lax.scan(epoch_fn, c, ts)

    scan_chunk = jax.jit(chunk, donate_argnums=(0,))
    return drive_epochs(
        lambda c, ts: scan_chunk(c, ts, data["images"], data["labels"]),
        init_carry(cfg, backend), cfg, backend, data,
    )


def run_batch(
    cfg: EHFLConfig,
    backend: Backend,
    data: Dict[str, jax.Array],
    seeds: Sequence[int] | jax.Array,
    use_kernel: bool = False,
) -> Dict[str, Any]:
    """Multi-seed sweep: the whole T-epoch simulation (periodic eval
    included) vmapped over a seed axis and executed as ONE jitted call.

    Seed i of the batch follows the exact same PRNG chain as
    ``run_simulation(dataclasses.replace(cfg, seed=seeds[i]), ...)`` — the
    slot-level integer dynamics are bit-identical; float trajectories agree
    up to compilation-order rounding.  ``data`` is shared across seeds (the
    standard multi-seed protocol: one partition, many scheduling runs).

    Returns the same dict shape as :func:`run_simulation` with a leading
    seed axis on every metric, ``global_params`` and ``carry`` leaf —
    except ``metrics["f1_epochs"]``, the eval schedule, which is shared
    across seeds and stays 1-D ``(n_evals,)``.
    """
    seeds = jnp.asarray(seeds, jnp.int32)
    from repro.models.cnn import macro_f1

    chunk = max(1, cfg.eval_every)
    n_full, rem = divmod(cfg.epochs, chunk)
    # the data is a jit argument shared across the seed axis, not a
    # closed-over constant (see run_simulation)
    data = {k: data[k] for k in ("images", "labels", "test_images", "test_labels")}

    def sweep(seeds, data):
        epoch_fn = make_epoch_fn(cfg, backend, data, use_kernel=use_kernel)

        def eval_f1(params):
            preds = backend.predict(params, data["test_images"])
            return macro_f1(preds, data["test_labels"], backend.num_classes)

        def solo(seed):
            carry = init_carry(cfg, backend, seed)
            ms_parts, f1_parts = [], []
            if n_full:
                def chunk_body(c, i):
                    c, ms = jax.lax.scan(epoch_fn, c, i * chunk + jnp.arange(chunk))
                    return c, (ms, eval_f1(c.global_params))

                carry, (ms, f1s) = jax.lax.scan(chunk_body, carry, jnp.arange(n_full))
                ms_parts.append(
                    jax.tree.map(lambda x: x.reshape((n_full * chunk,) + x.shape[2:]), ms)
                )
                f1_parts.append(f1s)
            if rem:
                carry, ms_tail = jax.lax.scan(
                    epoch_fn, carry, jnp.arange(n_full * chunk, cfg.epochs)
                )
                ms_parts.append(ms_tail)
                f1_parts.append(eval_f1(carry.global_params)[None])
            metrics = (
                jax.tree.map(lambda *xs: jnp.concatenate(xs), *ms_parts)
                if len(ms_parts) > 1
                else ms_parts[0]
            )
            metrics = dict(metrics)
            metrics["f1"] = jnp.concatenate(f1_parts) if len(f1_parts) > 1 else f1_parts[0]
            return carry, metrics

        return jax.vmap(solo)(seeds)

    carries, metrics = jax.jit(sweep)(seeds, data)
    metrics["f1_epochs"] = jnp.asarray(
        [chunk * (i + 1) for i in range(n_full)] + ([cfg.epochs] if rem else [])
    )
    metrics["total_energy"] = jnp.sum(metrics["energy"], axis=-1)  # (R,)
    return {"metrics": metrics, "global_params": carries.global_params, "carry": carries}
