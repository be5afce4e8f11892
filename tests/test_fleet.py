"""Client-sharded fleet simulator (core/fleet.py, DESIGN.md §9).

The correctness contract: a sharded run matches the single-device
``run_simulation`` for any N divisible by the shard count — integer slot
dynamics (batteries, uploads, starts) and VAoI ages EXACTLY, float
trajectories (f1, avg_m) to fp32 rounding (macro-F1 is an argmax metric, so
last-ulp parameter differences can flip individual test predictions).

On one device this still exercises the whole shard_map/psum/all-gather
machinery with a single shard; the CI multi-device leg reruns it under
``XLA_FLAGS=--xla_force_host_platform_device_count=8``.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.configs.cifar_cnn import CNNConfig
from repro.core import EHFLConfig, run_fleet, run_simulation
from repro.core import harvest as harvest_lib
from repro.core import policies as policy_lib
from repro.core import vaoi as vaoi_lib
from repro.core.simulator import _masked_mean, _masked_mean_kernel
from repro.data import make_federated_dataset
from repro.fl import cnn_backend
from repro.launch.mesh import make_fleet_mesh

TINY_CNN = CNNConfig(
    name="tiny", image_size=16, conv_channels=(4, 4, 8, 8, 8, 8), fc_dims=(32, 16)
)


@pytest.fixture(scope="module")
def backend():
    return cnn_backend(TINY_CNN)


@pytest.fixture(scope="module")
def worlds():
    return {
        n: make_federated_dataset(
            jax.random.PRNGKey(0), num_clients=n, samples_per_client=40,
            alpha=0.5, test_size=100, image_size=16,
        )
        for n in (16, 64)
    }


def _cfg(n, **kw):
    base = dict(
        num_clients=n, epochs=4, slots_per_epoch=12, kappa=8, p_bc=0.6,
        k=3, mu=0.1, e_max=13, eval_every=4, probe_size=10,
    )
    base.update(kw)
    return EHFLConfig(**base)


def _assert_fleet_matches_solo(cfg, backend, data, use_kernel=False):
    solo = run_simulation(cfg, backend, data, use_kernel=use_kernel)
    fleet = run_fleet(cfg, backend, data, use_kernel=use_kernel)
    ms, mf = solo["metrics"], fleet["metrics"]
    for k in (
        "energy", "n_started", "n_uploaded", "n_delivered", "n_failed",
        "n_dropped", "avg_age", "f1_epochs",
    ):
        np.testing.assert_array_equal(np.asarray(ms[k]), np.asarray(mf[k]), err_msg=k)
    # the continuous quantities agree to fp32 rounding *amplified by
    # training*: psum vs full-axis summation order differs in the last ulp,
    # and kappa SGD steps per epoch grow that deterministically (measured
    # max drift ~3e-3 after 4 epochs across all policy/scenario combos)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-2
        ),
        solo["global_params"],
        fleet["global_params"],
    )
    np.testing.assert_allclose(np.asarray(ms["avg_m"]), np.asarray(mf["avg_m"]), atol=1e-3)
    # macro-F1 is discrete (argmax over a 100-point test set): last-ulp
    # parameter differences can flip individual predictions, so its
    # granularity — not fp32 — sets the tolerance
    np.testing.assert_allclose(np.asarray(ms["f1"]), np.asarray(mf["f1"]), atol=0.1)
    for f in ("age", "battery", "pending", "counter", "retries", "backoff"):
        np.testing.assert_array_equal(
            np.asarray(getattr(solo["carry"], f)),
            np.asarray(getattr(fleet["carry"], f)),
            err_msg=f"carry.{f}",
        )


# a latin square over (N, policy, harvest scenario, data stream, uplink
# channel): every policy, harvest scenario, stream scenario, and channel
# scenario runs end to end, both fleet sizes see a spread of each, without
# the full 5x4x4x4x2 cross
_CHANNEL_PARAMS = {
    "ideal": (),
    "erasure": (("p_loss", 0.4),),
    "aloha": (("num_channels", 2.0),),
    "fading": (("p_bad", 0.4), ("sojourn", 2.0)),
}


@pytest.mark.parametrize(
    "n,policy,scenario,stream,channel",
    [
        (16, "vaoi", "bernoulli", "static", "ideal"),
        (16, "fedbacys", "markov", "drift", "erasure"),
        (16, "fedbacys_odd", "diurnal", "arrival", "aloha"),
        (16, "vaoi_soft", "hetero", "shift", "fading"),
        (64, "vaoi", "markov", "arrival", "erasure"),
        (64, "fedbacys", "bernoulli", "shift", "aloha"),
        (64, "fedavg", "hetero", "drift", "fading"),
    ],
)
def test_fleet_matches_solo(n, policy, scenario, stream, channel, worlds, backend):
    cfg = _cfg(
        n, policy=policy, harvest=scenario, stream=stream,
        stream_params=(("period", 3.0),) if stream in ("drift", "shift") else (),
        channel=channel, channel_params=_CHANNEL_PARAMS[channel],
    )
    _assert_fleet_matches_solo(cfg, backend, worlds[n])


def test_fleet_kernel_path_matches_solo(worlds, backend):
    """use_kernel=True end to end: the Pallas vaoi_distance + fedavg_reduce
    kernels run per shard inside shard_map."""
    cfg = _cfg(16, policy="vaoi")
    _assert_fleet_matches_solo(cfg, backend, worlds[16], use_kernel=True)


def test_masked_mean_kernel_matches_reference(rng):
    """Satellite: the fedavg_reduce-backed aggregation equals _masked_mean
    on a ragged pytree, including the no-uploads fallback."""
    ks = jax.random.split(rng, 4)
    stacked = {
        "w": jax.random.normal(ks[0], (12, 5, 3)),
        "b": jax.random.normal(ks[1], (12, 7)),
    }
    fallback = {"w": jax.random.normal(ks[2], (5, 3)), "b": jax.random.normal(ks[3], (7,))}
    mask = jnp.arange(12) % 3 == 0
    ref = _masked_mean(stacked, mask, fallback)
    ker = _masked_mean_kernel(stacked, mask, fallback)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6), ref, ker
    )
    none = jnp.zeros((12,), bool)
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(a, b),
        _masked_mean_kernel(stacked, none, fallback),
        fallback,
    )


@pytest.mark.parametrize("scenario", harvest_lib.SCENARIOS)
def test_sharded_harvest_matches_global(scenario):
    """make_sharded_process draws are bit-identical to the global process
    (the global-draw-and-slice recipe, incl. the bernoulli==uniform<p
    identity the probability-vector scenarios rely on)."""
    n, steps = 16, 6
    mesh = make_fleet_mesh(num_clients=n)
    solo = harvest_lib.make_process(scenario, p_bc=0.4)
    shp = harvest_lib.make_sharded_process(
        scenario, p_bc=0.4, axis_name="data", n_global=n
    )
    key = jax.random.PRNGKey(3)
    battery = jnp.zeros((n,), jnp.int32)

    def roll(process, bat):
        state = process.init(key, bat.shape[0])
        cs = []
        for _ in range(steps):
            c, state = process.step(state, bat)
            cs.append(c)
        return jnp.stack(cs)

    want = roll(solo, battery)
    got = jax.jit(
        shard_map(
            lambda b: roll(shp, b), mesh=mesh, in_specs=P("data"),
            out_specs=P(None, "data"), check_vma=False,
        )
    )(battery)
    np.testing.assert_array_equal(np.asarray(want), np.asarray(got), err_msg=scenario)


@pytest.mark.parametrize("k", [1, 3, 10, 16])
def test_distributed_topk_matches_global(k, rng):
    """Distributed Alg. 2 == single-device select_topk, including the k >
    shard-size regime and the all-zero cold start (pure-noise scores)."""
    n = 16
    mesh = make_fleet_mesh(num_clients=n)
    for age in (
        jax.random.randint(rng, (n,), 0, 5).astype(jnp.float32),
        jnp.zeros((n,), jnp.float32),
    ):
        key = jax.random.fold_in(rng, k)
        want = vaoi_lib.select_topk(age, k, key)
        got = jax.jit(
            shard_map(
                lambda a: vaoi_lib.select_topk_sharded(
                    a, k, key, axis_name="data", n_global=n
                ),
                mesh=mesh, in_specs=P("data"), out_specs=P("data"), check_vma=False,
            )
        )(age)
        np.testing.assert_array_equal(np.asarray(want), np.asarray(got))


@pytest.mark.parametrize("policy", ["vaoi_soft", "fedbacys", "fedbacys_odd", "fedavg"])
def test_epoch_selection_sharded_matches_global(policy, rng):
    n, k = 16, 4
    mesh = make_fleet_mesh(num_clients=n)
    spec = policy_lib.make_policy(policy, num_clients=n, k=k, num_groups=3)
    age = jax.random.randint(rng, (n,), 0, 6).astype(jnp.float32)
    for t in (0, 1, 5):
        epoch = jnp.asarray(t)
        key = jax.random.fold_in(rng, t)
        want = policy_lib.epoch_selection(spec, age, epoch, k, key)
        got = jax.jit(
            shard_map(
                lambda a: policy_lib.epoch_selection_sharded(
                    spec, a, epoch, k, key, axis_name="data", n_global=n
                ),
                mesh=mesh, in_specs=P("data"), out_specs=P("data"), check_vma=False,
            )
        )(age)
        np.testing.assert_array_equal(np.asarray(want), np.asarray(got), err_msg=policy)


def test_num_groups_threads_through_config(worlds, backend):
    """Satellite: EHFLConfig.num_groups reaches make_policy — FedBacys with
    G=2 schedules N/2 clients per epoch vs N/4 at the k-derived default.
    (p_bc=1, kappa=4: batteries reach kappa well before the S-kappa start
    deadline, so every scheduled client trains.)"""
    base = _cfg(16, policy="fedbacys", p_bc=1.0, k=4, kappa=4, epochs=1, eval_every=1)
    assert base.num_groups == 0  # default: G = N // k = 4
    small_g = dataclasses.replace(base, num_groups=2)
    n_default = int(np.asarray(run_simulation(base, backend, worlds[16])["metrics"]["n_started"])[0])
    n_small = int(np.asarray(run_simulation(small_g, backend, worlds[16])["metrics"]["n_started"])[0])
    assert n_default == 4 and n_small == 8


def test_run_fleet_validates_mesh(worlds, backend):
    cfg = _cfg(16)
    with pytest.raises(ValueError):  # no "data" axis
        run_fleet(cfg, backend, worlds[16], mesh=jax.make_mesh((1,), ("model",)))
    n_dev = len(jax.devices())
    if n_dev > 1:  # indivisible fleet (only constructible multi-device)
        with pytest.raises(ValueError):
            run_fleet(
                dataclasses.replace(cfg, num_clients=n_dev + 1), backend, worlds[16],
                mesh=jax.make_mesh((n_dev,), ("data",)),
            )
