"""Streaming non-IID data engine (repro/data/stream.py, DESIGN.md §10).

Contracts under test:
  * the ``static`` stream is the frozen-partition seed behavior BIT-FOR-BIT
    (no state, no PRNG consumption, identity view — the trajectory equals an
    epoch body with the stream machinery removed entirely);
  * per-scenario invariants: rotating label marginals for ``drift``, window
    occupancy/freshness for ``arrival``, scheduled class swaps for ``shift``;
  * the sharded stream (``make_sharded_stream``) is bit-identical to the
    solo stream — the fleet global-draw-and-slice contract (rerun on 8
    virtual devices by the CI multi-device leg);
  * every scenario runs end to end through ``run_simulation``.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.configs.cifar_cnn import CNNConfig
from repro.core import EHFLConfig, run_simulation
from repro.core import policies as policy_lib
from repro.core.simulator import epoch_body, init_carry, make_epoch_fn, solo_ops
from repro.data import make_federated_dataset
from repro.data import stream as stream_lib
from repro.fl import cnn_backend
from repro.launch.mesh import make_fleet_mesh

TINY_CNN = CNNConfig(
    name="tiny", image_size=16, conv_channels=(4, 4, 8, 8, 8, 8), fc_dims=(32, 16)
)


@pytest.fixture(scope="module")
def backend():
    return cnn_backend(TINY_CNN)


@pytest.fixture(scope="module")
def world():
    return make_federated_dataset(
        jax.random.PRNGKey(0), num_clients=8, samples_per_client=40,
        alpha=0.5, test_size=100, image_size=16,
    )


def _cfg(**kw):
    base = dict(
        num_clients=8, epochs=4, slots_per_epoch=12, kappa=8, p_bc=0.8,
        k=3, mu=0.1, e_max=13, eval_every=4, probe_size=10,
    )
    base.update(kw)
    return EHFLConfig(**base)


def _balanced_labels(n_clients: int, n_pool: int, num_classes: int = 10) -> jax.Array:
    """Every client holds an equal slice of every class."""
    return jnp.tile(jnp.arange(n_pool, dtype=jnp.int32) % num_classes, (n_clients, 1))


def _roll(stream, labels, steps, key=None, n=None):
    """Init + step a stream for ``steps`` epochs; returns (idx list, states)."""
    key = jax.random.PRNGKey(7) if key is None else key
    state = stream.init(key, labels.shape[0] if n is None else n)
    idxs, states = [], []
    for t in range(steps):
        idx, state = stream.step(state, jnp.asarray(t, jnp.int32), labels)
        idxs.append(idx)
        states.append(state)
    return idxs, states


def _marginal(labels, idx, num_classes=10):
    view = np.asarray(jnp.take_along_axis(labels, idx, axis=1)).ravel()
    return np.bincount(view, minlength=num_classes) / view.size


# ---------------------------------------------------------------------------
# static: the frozen partition, bit-for-bit
# ---------------------------------------------------------------------------


def test_static_stream_is_stateless_and_keyless(world, backend):
    st = stream_lib.make_stream("static")
    assert not st.persistent
    assert st.init(jax.random.PRNGKey(0), 8) is None
    idx, state = st.step(None, jnp.asarray(0), _balanced_labels(4, 20))
    assert idx is None and state is None
    # init_carry consumes no stream key: the carry key chain equals the
    # pre-stream chain (PRNGKey -> split -> k_run, bernoulli harvest adds
    # no split either)
    cfg = _cfg()
    carry = init_carry(cfg, backend)
    _, k_run = jax.random.split(jax.random.PRNGKey(cfg.seed))
    np.testing.assert_array_equal(np.asarray(carry.key), np.asarray(k_run))
    assert carry.stream is None


def test_static_bitmatches_seed_epoch_body(world, backend):
    """The full static-stream trajectory equals an epoch body with the
    stream machinery REMOVED (stream=None) — i.e., the seed run_simulation
    path — bit for bit: metrics AND final parameters."""
    cfg = _cfg(policy="vaoi")
    assert cfg.stream == "static"  # the default IS the paper protocol
    epoch_fn = make_epoch_fn(cfg, backend, world)
    spec = policy_lib.make_policy(cfg.policy, num_clients=cfg.num_clients, k=cfg.k)
    seed_fn = lambda c, t: epoch_body(
        c, t, world["images"], world["labels"],
        cfg=cfg, backend=backend, spec=spec, process=cfg.harvest_process(),
        ops=solo_ops(cfg), stream=None,
    )
    ts = jnp.arange(cfg.epochs)
    carry_a, ms_a = jax.jit(lambda c: jax.lax.scan(epoch_fn, c, ts))(init_carry(cfg, backend))
    carry_b, ms_b = jax.jit(lambda c: jax.lax.scan(seed_fn, c, ts))(init_carry(cfg, backend))
    for k in ms_a:
        np.testing.assert_array_equal(np.asarray(ms_a[k]), np.asarray(ms_b[k]), err_msg=k)
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b)),
        carry_a.global_params, carry_b.global_params,
    )


# ---------------------------------------------------------------------------
# drift: rotating label mixtures
# ---------------------------------------------------------------------------


def test_rotate_mixture_is_periodic_and_shifts():
    pi = jax.random.dirichlet(jax.random.PRNGKey(1), jnp.full((10,), 0.5), (4,))
    period = 20.0
    at = lambda t: stream_lib.rotate_mixture(pi, jnp.asarray(t, jnp.int32), period)
    np.testing.assert_allclose(np.asarray(at(0)), np.asarray(pi), atol=1e-6)
    np.testing.assert_allclose(np.asarray(at(20)), np.asarray(pi), atol=1e-6)
    # one integer class shift (t = period / C) is a circular roll
    np.testing.assert_allclose(
        np.asarray(at(2)), np.asarray(jnp.roll(pi, 1, axis=1)), atol=1e-6
    )
    # every rotation is still a distribution
    np.testing.assert_allclose(np.asarray(at(7)).sum(axis=1), 1.0, atol=1e-5)


def test_drift_label_marginal_rotates():
    labels = _balanced_labels(4, 400)
    stream = stream_lib.make_stream("drift", period=8.0, alpha=0.3)
    idxs, _ = _roll(stream, labels, 9)
    m0, m4, m8 = (_marginal(labels, idxs[t]) for t in (0, 4, 8))
    # half a period away the mixture has rotated C/2 classes: the view
    # marginal moves by a substantial total-variation distance...
    assert 0.5 * np.abs(m0 - m4).sum() > 0.2
    # ...and a full period later it is back (same mixture, fresh noise)
    assert 0.5 * np.abs(m0 - m8).sum() < 0.5 * np.abs(m0 - m4).sum()
    # idx maps stay within the pool
    for idx in idxs:
        assert int(idx.min()) >= 0 and int(idx.max()) < labels.shape[1]


# ---------------------------------------------------------------------------
# arrival: sliding-window sample arrivals
# ---------------------------------------------------------------------------


def test_arrival_window_occupancy_and_freshness():
    n_clients, n_pool, window = 8, 32, 12
    labels = _balanced_labels(n_clients, n_pool)
    stream = stream_lib.make_stream("arrival", rate=3.0, burst=2.0, window=window)
    idxs, states = _roll(stream, labels, 25)
    prev = np.ones((n_clients,), np.int64)  # warm = 1
    for idx, (count, _key) in zip(idxs, states):
        count = np.asarray(count)
        occ = np.asarray(stream_lib.arrival_occupancy(jnp.asarray(count), window, n_pool))
        assert (count >= prev).all()  # arrivals only accumulate
        assert (occ >= 1).all() and (occ <= window).all()
        for i in range(n_clients):
            seen = set(np.asarray(idx[i]).tolist())
            # the view covers EXACTLY the occupied window: the occ most
            # recent arrivals (stream position mod pool), nothing else
            want = {int((count[i] - 1 - j) % n_pool) for j in range(occ[i])}
            assert seen == want
        prev = count
    # mean arrivals/epoch tracks the configured rate (generous statistical
    # band; 8 clients x 25 epochs)
    total = float(np.asarray(states[-1][0]).sum() - n_clients)
    mean_rate = total / (n_clients * len(idxs))
    assert 1.5 < mean_rate < 4.5


def test_arrival_full_pool_window_defaults():
    labels = _balanced_labels(2, 16)
    stream = stream_lib.make_stream("arrival", rate=100.0)  # saturate fast
    idxs, states = _roll(stream, labels, 8)
    count = np.asarray(states[-1][0])
    assert (count > 16).all()  # wrapped: stream longer than the pool
    # saturated window == whole pool: the view is a permutation of the pool
    assert [sorted(np.asarray(idxs[-1][i]).tolist()) for i in range(2)] == [
        list(range(16))
    ] * 2


# ---------------------------------------------------------------------------
# shift: class-incremental swaps
# ---------------------------------------------------------------------------


def test_shift_swaps_active_classes_on_schedule():
    labels = _balanced_labels(4, 200)
    period, phases = 4, 2
    stream = stream_lib.make_stream("shift", period=period, num_phases=phases)
    idxs, _ = _roll(stream, labels, 2 * period)
    for t, idx in enumerate(idxs):
        phase = (t // period) % phases
        view = np.asarray(jnp.take_along_axis(labels, idx, axis=1))
        groups = np.asarray(stream_lib.class_group(jnp.asarray(view), phases, 10))
        assert (groups == phase).all(), f"epoch {t}: classes outside phase {phase}"
    # the swap happens exactly at the period boundary
    m_before = _marginal(labels, idxs[period - 1])
    m_after = _marginal(labels, idxs[period])
    assert m_before[:5].sum() > 0.99 and m_after[5:].sum() > 0.99


def test_shift_uniform_fallback_when_no_active_samples():
    # client 0 holds ONLY class 0 (group 0): at phase 1 it has no active
    # samples and falls back to a uniform view of its pool
    labels = jnp.zeros((1, 50), jnp.int32)
    stream = stream_lib.make_stream("shift", period=1, num_phases=2)
    idxs, _ = _roll(stream, labels, 2)
    idx = np.asarray(idxs[1])  # t=1 -> phase 1, nothing active
    assert idx.min() >= 0 and idx.max() < 50
    assert len(set(idx.ravel().tolist())) > 10  # spread, not a constant fill


# ---------------------------------------------------------------------------
# sharded == solo (the fleet contract, DESIGN.md §9/§10)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("scenario", ["drift", "arrival", "shift"])
def test_sharded_stream_matches_solo(scenario):
    """make_sharded_stream draws are bit-identical to the solo stream under
    shard_map — init state AND every per-epoch idx map."""
    n, n_pool, steps = 16, 24, 5
    params = {"drift": {"period": 6.0}, "arrival": {"rate": 2.5, "window": 8.0},
              "shift": {"period": 2.0}}[scenario]
    mesh = make_fleet_mesh(num_clients=n)
    labels = _balanced_labels(n, n_pool)
    solo = stream_lib.make_stream(scenario, **params)
    shp = stream_lib.make_sharded_stream(
        scenario, axis_name="data", n_global=n, **params
    )
    key = jax.random.PRNGKey(11)

    def roll(stream, lbls):
        state = stream.init(key, lbls.shape[0])
        out = []
        for t in range(steps):
            idx, state = stream.step(state, jnp.asarray(t, jnp.int32), lbls)
            out.append(idx)
        return jnp.stack(out)

    want = roll(solo, labels)
    got = jax.jit(
        shard_map(
            lambda l: roll(shp, l), mesh=mesh, in_specs=P("data", None),
            out_specs=P(None, "data", None), check_vma=False,
        )
    )(labels)
    np.testing.assert_array_equal(np.asarray(want), np.asarray(got), err_msg=scenario)


# ---------------------------------------------------------------------------
# end to end
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("scenario", stream_lib.SCENARIOS)
def test_streams_run_end_to_end(scenario, world, backend):
    cfg = _cfg(epochs=2, eval_every=2, stream=scenario)
    out = run_simulation(cfg, backend, world)
    m = out["metrics"]
    assert np.isfinite(np.asarray(m["f1"])).all()
    assert np.isfinite(np.asarray(m["avg_m"])).all()
    assert float(m["total_energy"]) >= 0


def test_backend_num_classes_threads_into_streams():
    """Class-conditioned streams pick up the dataset's class count from the
    backend (an explicit stream_params entry wins); 20-class labels must not
    be clamped into a 10-class mixture."""
    cfg = EHFLConfig(stream="drift")
    pi, _key = cfg.data_stream(num_classes=20).init(jax.random.PRNGKey(0), 4)
    assert pi.shape == (4, 20)
    cfg_explicit = dataclasses.replace(
        cfg, stream_params=(("num_classes", 5.0),)
    )
    pi5, _key = cfg_explicit.data_stream(num_classes=20).init(jax.random.PRNGKey(0), 4)
    assert pi5.shape == (4, 5)
    # shift: all 20 class groups cycle through the active phases
    labels = _balanced_labels(2, 400, num_classes=20)
    st = stream_lib.make_stream("shift", period=1, num_phases=2, num_classes=20)
    idxs, _ = _roll(st, labels, 2)
    seen = set()
    for t, idx in enumerate(idxs):
        view = np.asarray(jnp.take_along_axis(labels, idx, axis=1))
        seen |= set(view.ravel().tolist())
    assert seen == set(range(20))  # classes 10-19 are NOT silently excluded


def test_unknown_stream_raises(world, backend):
    with pytest.raises(ValueError):
        stream_lib.make_stream("nope")
    cfg = dataclasses.replace(_cfg(), stream="nope")
    with pytest.raises(ValueError):
        run_simulation(cfg, backend, world)
