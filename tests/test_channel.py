"""Lossy-uplink channel (repro/core/channel.py + the simulator's retry state
machine, DESIGN.md §12).

Contracts under test:
  * the ``ideal`` channel is the pre-channel simulator BIT-FOR-BIT (no
    state, no PRNG consumption — the trajectory equals an epoch body with
    the channel machinery removed entirely), across solo, ``run_batch``,
    and fleet drivers;
  * per-scenario delivery invariants: empirical erasure rates, ALOHA
    collision determinism (M=1 with >=2 contenders always collides, a lone
    contender always lands), fading outage in the bad link state;
  * the retry state machine: failed carriers re-queue with the capped
    exponential backoff schedule (skip min(2^(attempts-1), cap) epochs),
    drop after ``max_retries`` with no energy refund, and re-age their VAoI
    by exactly one version per failure;
  * the sharded channel (``make_sharded_channel``) is bit-identical to the
    solo channel — global-draw-and-slice, plus the psum'd ALOHA contention
    counts (rerun on 8 virtual devices by the CI multi-device leg).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.configs.cifar_cnn import CNNConfig
from repro.core import EHFLConfig, run_batch, run_simulation
from repro.core import channel as channel_lib
from repro.core import policies as policy_lib
from repro.core.simulator import epoch_body, init_carry, make_epoch_fn, solo_ops
from repro.data import make_federated_dataset
from repro.fl import cnn_backend
from repro.launch.mesh import make_fleet_mesh

TINY_CNN = CNNConfig(
    name="tiny", image_size=16, conv_channels=(4, 4, 8, 8, 8, 8), fc_dims=(32, 16)
)
N = 8


@pytest.fixture(scope="module")
def backend():
    return cnn_backend(TINY_CNN)


@pytest.fixture(scope="module")
def world():
    return make_federated_dataset(
        jax.random.PRNGKey(0), num_clients=N, samples_per_client=40,
        alpha=0.5, test_size=100, image_size=16,
    )


def _cfg(**kw):
    base = dict(
        num_clients=N, epochs=4, slots_per_epoch=12, kappa=8, p_bc=0.8,
        k=3, mu=0.1, e_max=13, eval_every=4, probe_size=10,
    )
    base.update(kw)
    return EHFLConfig(**base)


def _roll(chan, attempting, steps, key=None, n=None):
    """Init + step a channel for ``steps`` epochs on a fixed attempt mask."""
    key = jax.random.PRNGKey(7) if key is None else key
    state = chan.init(key, attempting.shape[0] if n is None else n)
    outs = []
    for _ in range(steps):
        d, state = chan.step(state, attempting)
        outs.append(d)
    return jnp.stack(outs), state


# ---------------------------------------------------------------------------
# ideal: the pre-channel simulator, bit-for-bit
# ---------------------------------------------------------------------------


def test_ideal_is_stateless_and_keyless(backend):
    ch = channel_lib.make_channel("ideal")
    assert not ch.persistent
    assert ch.init(jax.random.PRNGKey(0), N) is None
    att = jnp.array([True, False, True, False])
    d, state = ch.step(None, att)
    np.testing.assert_array_equal(np.asarray(d), np.asarray(att))
    assert state is None
    # init_carry consumes no channel key: the carry key chain equals the
    # pre-channel chain, and the retry state is born all-zero
    cfg = _cfg()
    assert cfg.channel == "ideal"  # the default IS the lossless protocol
    carry = init_carry(cfg, backend)
    _, k_run = jax.random.split(jax.random.PRNGKey(cfg.seed))
    np.testing.assert_array_equal(np.asarray(carry.key), np.asarray(k_run))
    assert carry.channel is None
    assert not np.asarray(carry.retries).any() and not np.asarray(carry.backoff).any()


def test_ideal_bitmatches_channelless_epoch_body(world, backend):
    """The full ideal-channel trajectory equals an epoch body with the
    channel machinery REMOVED (channel=None) — i.e., the pre-channel
    run_simulation path — bit for bit: metrics AND final parameters."""
    cfg = _cfg(policy="vaoi")
    epoch_fn = make_epoch_fn(cfg, backend, world)  # default channel: ideal
    spec = policy_lib.make_policy(cfg.policy, num_clients=cfg.num_clients, k=cfg.k)
    seed_fn = lambda c, t: epoch_body(
        c, t, world["images"], world["labels"],
        cfg=cfg, backend=backend, spec=spec, process=cfg.harvest_process(),
        ops=solo_ops(cfg), stream=None, channel=None,
    )
    ts = jnp.arange(cfg.epochs)
    carry_a, ms_a = jax.jit(lambda c: jax.lax.scan(epoch_fn, c, ts))(init_carry(cfg, backend))
    carry_b, ms_b = jax.jit(lambda c: jax.lax.scan(seed_fn, c, ts))(init_carry(cfg, backend))
    for k in ms_a:
        np.testing.assert_array_equal(np.asarray(ms_a[k]), np.asarray(ms_b[k]), err_msg=k)
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b)),
        carry_a, carry_b,
    )
    # uploads always land under ideal
    np.testing.assert_array_equal(
        np.asarray(ms_a["n_delivered"]), np.asarray(ms_a["n_uploaded"])
    )
    assert not np.asarray(ms_a["n_failed"]).any()


# ---------------------------------------------------------------------------
# per-scenario delivery invariants
# ---------------------------------------------------------------------------


def test_erasure_empirical_loss_rate():
    n, steps, p = 512, 40, 0.3
    ch = channel_lib.make_channel("erasure", p_loss=p)
    delivered, _ = _roll(ch, jnp.ones((n,), bool), steps)
    rate = float(np.asarray(delivered).mean())
    assert abs(rate - (1.0 - p)) < 0.02
    # non-attempting clients never deliver
    att = jnp.arange(n) % 2 == 0
    delivered, _ = _roll(ch, att, 5)
    assert not np.asarray(delivered[:, 1::2]).any()


def test_erasure_hetero_rates():
    """concentration > 0 draws static per-client loss rates (mean p_loss)."""
    n = 2048
    ch = channel_lib.make_channel("erasure", p_loss=0.3, concentration=1.0)
    rates, _ = ch.init(jax.random.PRNGKey(1), n)
    rates = np.asarray(rates)
    assert abs(rates.mean() - 0.3) < 0.03
    assert rates.std() > 0.1  # genuinely heterogeneous links
    assert (rates >= 0).all() and (rates <= 1).all()


def test_aloha_collision_determinism():
    """M=1: two contenders ALWAYS collide, a lone contender ALWAYS lands."""
    ch = channel_lib.make_channel("aloha", num_channels=1)
    two = jnp.array([True, True, False, False])
    delivered, _ = _roll(ch, two, 10)
    assert not np.asarray(delivered).any()
    one = jnp.array([False, False, True, False])
    delivered, _ = _roll(ch, one, 10)
    np.testing.assert_array_equal(
        np.asarray(delivered), np.broadcast_to(np.asarray(one), (10, 4))
    )


def test_aloha_empirical_throughput():
    """All-contend delivery rate matches slotted-ALOHA theory:
    P(deliver) = (1 - 1/M)^(n-1)."""
    n, M, steps = 16, 8, 400
    ch = channel_lib.make_channel("aloha", num_channels=M)
    delivered, _ = _roll(ch, jnp.ones((n,), bool), steps)
    want = (1.0 - 1.0 / M) ** (n - 1)
    assert abs(float(np.asarray(delivered).mean()) - want) < 0.03


def test_fading_outage_extremes():
    att = jnp.ones((32,), bool)
    always_bad = channel_lib.make_channel("fading", p_bad=1.0)
    delivered, _ = _roll(always_bad, att, 8)
    assert not np.asarray(delivered).any()
    always_good = channel_lib.make_channel("fading", p_bad=0.0)
    delivered, _ = _roll(always_good, att, 8)
    assert np.asarray(delivered).all()


def test_fading_stationary_fraction():
    n, steps, pb = 256, 80, 0.4
    ch = channel_lib.make_channel("fading", p_bad=pb, sojourn=2.0)
    delivered, _ = _roll(ch, jnp.ones((n,), bool), steps)
    rate = float(np.asarray(delivered).mean())
    assert abs(rate - (1.0 - pb)) < 0.05
    # bursty: consecutive epochs of the same link state correlate
    d = np.asarray(delivered)
    agree = (d[1:] == d[:-1]).mean()
    assert agree > 0.6  # i.i.d. would sit at p^2 + (1-p)^2 = 0.52


def test_unknown_channel_raises():
    with pytest.raises(ValueError):
        channel_lib.make_channel("carrier-pigeon")
    with pytest.raises(ValueError):
        channel_lib.make_sharded_channel("x", axis_name="data", n_global=8)


# ---------------------------------------------------------------------------
# retry state machine: backoff schedule, max_retries drop, VAoI re-aging
# ---------------------------------------------------------------------------


def _epoch_stepper(cfg, backend, world, channel):
    spec = policy_lib.make_policy(cfg.policy, num_clients=cfg.num_clients, k=cfg.k)
    fn = lambda c, t: epoch_body(
        c, t, world["images"], world["labels"],
        cfg=cfg, backend=backend, spec=spec, process=cfg.harvest_process(),
        ops=solo_ops(cfg), stream=None, channel=channel,
    )
    return jax.jit(fn)


def test_backoff_schedule_and_max_retries_drop(world, backend):
    """p_loss=1: every attempt fails.  The carrier walks the capped
    exponential schedule — attempt, skip 2^(attempts-1) epochs, re-attempt —
    and is dropped (pending cleared, counters reset) after max_retries,
    with every transmission unit of energy spent and none refunded."""
    cfg = _cfg(
        policy="fedavg", p_bc=1.0, kappa=2, slots_per_epoch=8, e_max=8,
        channel="erasure", channel_params=(("p_loss", 1.0),),
        max_retries=3, backoff_cap=8,
    )
    ch = cfg.channel_process()
    step = _epoch_stepper(cfg, backend, world, ch)
    carry = init_carry(cfg, backend)
    seen = []
    for t in range(8):
        carry, ms = step(carry, jnp.asarray(t))
        seen.append({
            "uploaded": int(ms["n_uploaded"]) // N,  # homogeneous clients
            "delivered": int(ms["n_delivered"]),
            "dropped": int(ms["n_dropped"]) // N,
            "retries": int(np.asarray(carry.retries)[0]),
            "backoff": int(np.asarray(carry.backoff)[0]),
            "pending": bool(np.asarray(carry.pending)[0]),
        })
    # epoch 0: attempt 1 fails -> retries=1, skip 2^0=1 epoch
    # epoch 2: attempt 2 fails -> retries=2, skip 2^1=2 epochs
    # epoch 5: attempt 3 fails -> max_retries hit -> DROP (counters reset);
    #          having uploaded early in the epoch the client is free again
    #          (not pending, never started this epoch) and trains a FRESH
    #          update — the seed old-carrier semantics — so it ends the
    #          drop epoch pending a new message with a clean retry count
    # epoch 6: the fresh message starts its own retry ladder
    want = [
        dict(uploaded=1, retries=1, backoff=1, pending=True, dropped=0),
        dict(uploaded=0, retries=1, backoff=0, pending=True, dropped=0),
        dict(uploaded=1, retries=2, backoff=2, pending=True, dropped=0),
        dict(uploaded=0, retries=2, backoff=1, pending=True, dropped=0),
        dict(uploaded=0, retries=2, backoff=0, pending=True, dropped=0),
        dict(uploaded=1, retries=0, backoff=0, pending=True, dropped=1),
        dict(uploaded=1, retries=1, backoff=1, pending=True, dropped=0),
    ]
    for t, w in enumerate(want):
        got = {k: seen[t][k] for k in w}
        assert got == w, f"epoch {t}: {got} != {w}"
    assert all(s["delivered"] == 0 for s in seen)  # p_loss=1 delivers nothing


def test_backoff_cap_clamps_schedule(world, backend):
    """backoff_cap bounds the skip length: with cap=1 the carrier re-attempts
    every other epoch regardless of the attempt count."""
    cfg = _cfg(
        policy="fedavg", p_bc=1.0, kappa=2, slots_per_epoch=8, e_max=8,
        channel="erasure", channel_params=(("p_loss", 1.0),),
        max_retries=100, backoff_cap=1,
    )
    step = _epoch_stepper(cfg, backend, world, cfg.channel_process())
    carry = init_carry(cfg, backend)
    uploads = []
    for t in range(6):
        carry, ms = step(carry, jnp.asarray(t))
        uploads.append(int(ms["n_uploaded"]) // N)
        assert int(np.asarray(carry.backoff).max()) <= 1
    assert uploads == [1, 0, 1, 0, 1, 0]


def test_vaoi_reaging_is_exactly_one_version_per_failure(world, backend):
    """One epoch, same carry, same PRNG chain (the channel owns its own key
    chain): the lossy ages equal the ideal ages + the failed mask, the
    delivery mask gates aggregation (global model falls back), and failed
    carriers re-queue."""
    cfg = _cfg(policy="vaoi", p_bc=1.0, kappa=2, slots_per_epoch=8, e_max=8)
    lossy_cfg = dataclasses.replace(
        cfg, channel="erasure", channel_params=(("p_loss", 1.0),)
    )
    carry = init_carry(cfg, backend)  # ideal config: no channel key split
    lossy_ch = lossy_cfg.channel_process()
    carry_lossy = carry._replace(channel=lossy_ch.init(jax.random.PRNGKey(42), N))

    c_i, m_i = _epoch_stepper(cfg, backend, world, cfg.channel_process())(
        carry, jnp.asarray(0)
    )
    c_l, m_l = _epoch_stepper(lossy_cfg, backend, world, lossy_ch)(
        carry_lossy, jnp.asarray(0)
    )
    assert int(m_i["n_uploaded"]) == int(m_l["n_uploaded"]) > 0
    assert int(m_l["n_delivered"]) == 0 and int(m_l["n_failed"]) > 0
    failed = np.asarray(c_l.retries) > 0
    assert failed.sum() == int(m_l["n_failed"])
    # re-age: exactly +1 version per failed upload, bitwise elsewhere
    np.testing.assert_array_equal(
        np.asarray(c_l.age), np.asarray(c_i.age) + failed.astype(np.float32)
    )
    # nothing landed -> the global model fell back to the incoming params
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b)),
        c_l.global_params, carry.global_params,
    )
    # failed carriers hold their message for retransmission
    assert np.asarray(c_l.pending)[failed].all()


def test_lossy_mean_age_dominates_ideal(world, backend):
    """Aggregate re-aging direction: under heavy loss the fleet's mean VAoI
    sits above the lossless run's (the scheduler sees honest staleness)."""
    base = _cfg(policy="vaoi", epochs=12, eval_every=12)
    lossy = dataclasses.replace(
        base, channel="erasure", channel_params=(("p_loss", 0.8),)
    )
    age_i = float(np.asarray(run_simulation(base, backend, world)["metrics"]["avg_age"]).mean())
    age_l = float(np.asarray(run_simulation(lossy, backend, world)["metrics"]["avg_age"]).mean())
    assert age_l > age_i


# ---------------------------------------------------------------------------
# sharded == solo (global-draw-and-slice + ALOHA contention psum)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("scenario,params", [
    ("ideal", {}),
    ("erasure", {"p_loss": 0.4, "concentration": 1.0}),
    ("aloha", {"num_channels": 2}),
    ("fading", {"p_bad": 0.4, "sojourn": 2.0}),
])
def test_sharded_channel_matches_global(scenario, params, rng):
    n, steps = 16, 6
    mesh = make_fleet_mesh(num_clients=n)
    solo = channel_lib.make_channel(scenario, **params)
    shp = channel_lib.make_sharded_channel(
        scenario, axis_name="data", n_global=n, **params
    )
    key = jax.random.PRNGKey(3)
    # a different contention pattern every step (exercises ALOHA's psum)
    atts = jax.random.bernoulli(rng, 0.6, (steps, n))

    def roll(chan, att_rows):
        state = chan.init(key, att_rows.shape[1])
        ds = []
        for i in range(steps):
            d, state = chan.step(state, att_rows[i])
            ds.append(d)
        return jnp.stack(ds)

    want = roll(solo, atts)
    got = jax.jit(
        shard_map(
            lambda a: roll(shp, a), mesh=mesh, in_specs=P(None, "data"),
            out_specs=P(None, "data"), check_vma=False,
        )
    )(atts)
    np.testing.assert_array_equal(np.asarray(want), np.asarray(got), err_msg=scenario)


# ---------------------------------------------------------------------------
# end to end through every driver
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("scenario,params", [
    ("erasure", (("p_loss", 0.5),)),
    ("aloha", (("num_channels", 1.0),)),
    ("fading", (("p_bad", 0.6), ("sojourn", 2.0))),
])
def test_lossy_end_to_end(scenario, params, world, backend):
    cfg = _cfg(policy="vaoi", channel=scenario, channel_params=params)
    m = run_simulation(cfg, backend, world)["metrics"]
    up, dl, fa = (int(np.asarray(m[k]).sum()) for k in ("n_uploaded", "n_delivered", "n_failed"))
    assert up == dl + fa and fa > 0  # the channel actually bites


def test_run_batch_matches_solo_under_loss(world, backend):
    """The seed-vmapped driver follows the same lossy chain bit-for-bit on
    the integer dynamics."""
    cfg = _cfg(policy="vaoi", channel="erasure", channel_params=(("p_loss", 0.5),))
    solo = run_simulation(cfg, backend, world)
    batch = run_batch(cfg, backend, world, seeds=[cfg.seed])
    for k in ("energy", "n_started", "n_uploaded", "n_delivered", "n_failed",
              "n_dropped", "avg_age"):
        np.testing.assert_array_equal(
            np.asarray(solo["metrics"][k]), np.asarray(batch["metrics"][k])[0],
            err_msg=k,
        )
