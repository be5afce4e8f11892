"""The plain reference against the simulator's epoch body, on the CPU at a
tiny size, and the CNN family's data generator against the program's.

On the CPU the convolutions run in float32, so the simulator and the
reference make the same decisions: integer dynamics and VAoI ages must be
equal.  Floats may differ by the summation order of FedAvg and of the
convolutions (last-ulp differences that kappa SGD steps carry on), so they
are held to 1e-5 (params, absolute; their magnitude is about 0.1-1) and
1e-6 (mean distances)."""
from __future__ import annotations

import jax
import numpy as np
import pytest

from bench import compare, reference
from bench.calibrate import as_program
from bench.models import cnn
from bench.tests import tiny

EPOCHS = 12


def _program(policy, compact, seed, data):
    from repro.core import EHFLConfig, run_simulation

    cfg = EHFLConfig(**tiny.SIM, epochs=EPOCHS, policy=policy, compact=compact, seed=seed)
    return run_simulation(cfg, cnn.program_backend(tiny.MODEL), data)


@pytest.mark.parametrize("policy,compact", [
    ("vaoi", "auto"), ("vaoi", False), ("fedavg", "auto"), ("fedavg", False),
])
def test_reference_matches_epoch_body(policy, compact):
    seed = 5
    data = cnn.make_dataset(3, tiny.DATA, tiny.MODEL)
    out = _program(policy, compact, seed, data)
    pm = jax.tree.map(np.asarray, out["metrics"])
    ref = reference.simulate(tiny.SIM, cnn, tiny.MODEL, data, seed, EPOCHS, policy)
    rm = ref["metrics"]
    for k in ("n_started", "n_uploaded", "energy"):
        np.testing.assert_array_equal(pm[k], rm[k], err_msg=k)
    np.testing.assert_array_equal(compare.age_sums(pm["avg_age"], tiny.SIM["num_clients"]),
                                  rm["age_sum"])
    np.testing.assert_array_equal(np.asarray(out["carry"].age), ref["final"]["age"])
    np.testing.assert_array_equal(np.asarray(out["carry"].battery), ref["final"]["battery"])
    np.testing.assert_array_equal(np.asarray(out["carry"].pending), ref["final"]["pending"])
    np.testing.assert_allclose(pm["avg_m"], rm["avg_m"], atol=1e-6)
    np.testing.assert_allclose(pm["f1"], ref["f1"], atol=1e-6)
    for k, v in ref["global_params"].items():
        np.testing.assert_allclose(np.asarray(out["global_params"][k]), v, atol=1e-5, err_msg=k)
    assert rm["n_started"].sum() > 0
    if policy == "vaoi":
        assert rm["age_sum"].max() > 0, "ages never grew: the test would not exercise Eq. 7"


def test_lockstep_follows_the_witness():
    """An ambiguous decision is settled the program's way when the age sum
    pins it down, and ends the lockstep when it cannot; under VAoI the
    lockstep ends at the first epoch after a training."""
    data = cnn.make_dataset(3, tiny.DATA, tiny.MODEL)
    simulate = lambda **kw: reference.simulate(tiny.SIM, cnn, tiny.MODEL, data, 5, 6, "vaoi", **kw)
    base = simulate()
    sums = base["metrics"]["age_sum"]
    first = int(np.flatnonzero(base["metrics"]["n_started"])[0])
    held = simulate(witness=sums, margin=1e-6)
    assert held["t_stop"] == first + 1 and held["witness_faults"] == 0
    # every free client ambiguous, and a sum (1 of the 5 at epoch 0, when all
    # ages are 0) that no common decision gives: the lockstep ends, no fault
    split = sums.copy()
    split[0] = 1
    wide = simulate(witness=split, margin=10.0)
    assert wide["t_stop"] == 0 and wide["witness_faults"] == 0
    bad = sums.copy()
    bad[first] += 100
    off = simulate(witness=bad, margin=1e-6)
    assert off["witness_faults"] == 1 and off["t_stop"] == first


def test_control_fails_the_comparison():
    """The reference in bfloat16, in the program's place, reads over the
    tiny cell's limits."""
    data = cnn.make_dataset(4, tiny.DATA, tiny.MODEL)
    n = tiny.SIM["num_clients"]
    c = reference.simulate(tiny.SIM, cnn, tiny.MODEL, data, 4, EPOCHS, "vaoi",
                           numerics=reference.CONTROL)
    ref = reference.simulate(tiny.SIM, cnn, tiny.MODEL, data, 4, EPOCHS, "vaoi",
                             witness=compare.age_sums(c["metrics"]["avg_age"], n), margin=1e-4)
    f1 = reference.eval_f1(cnn, tiny.MODEL, c["global_params"], data)
    nums = compare.numbers(as_program(c), ref, f1, n)
    ok, _ = compare.judge(nums, {"int_mismatch": 0, "probe_m_gap": 1e-6, "param_change_gap": 1e-4})
    assert not ok, nums


def test_data_copy_matches_the_program_generator():
    """Labels equal; images to one float32 rounding (the copy runs as one
    jitted call, where ``proto + noise * eps`` may fuse into one
    multiply-add, while the program's generator runs op by op)."""
    from repro.data import make_federated_dataset

    d = tiny.DATA
    ours = cnn.make_dataset(11, d, tiny.MODEL)
    theirs = make_federated_dataset(
        jax.random.PRNGKey(11), num_clients=d["num_clients"],
        samples_per_client=d["samples_per_client"], alpha=d["alpha"], test_size=d["test_size"],
        image_size=tiny.MODEL["image_size"], noise=d["noise"])
    for k in ("labels", "test_labels"):
        np.testing.assert_array_equal(np.asarray(ours[k]), np.asarray(theirs[k]), err_msg=k)
    for k in ("images", "test_images"):
        np.testing.assert_allclose(np.asarray(ours[k]), np.asarray(theirs[k]), rtol=0, atol=2e-6,
                                   err_msg=k)


def test_sharded_generator_matches_unsharded():
    """The client-sharded form writes the same data, on whatever devices
    this process has (one CPU device unless XLA_FLAGS gives more)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    devs = jax.devices()
    n = len(devs)
    data_cfg = {**tiny.DATA, "num_clients": 4 * n}
    mesh = Mesh(np.array(devs), ("data",))
    sharded = cnn.make_dataset(9, data_cfg, tiny.MODEL,
                               sharding=NamedSharding(mesh, PartitionSpec("data")))
    plain = cnn.make_dataset(9, data_cfg, tiny.MODEL)
    assert len(sharded["images"].sharding.device_set) == n
    for k in plain:
        np.testing.assert_array_equal(np.asarray(sharded[k]), np.asarray(plain[k]), err_msg=k)


# The generator as it stood in ``bench/data.py`` before the model families,
# kept here to hold ``cnn.make_dataset`` to it bit for bit.
def _old_prototypes(key, num_classes, image_size, channels):
    k1, _ = jax.random.split(key)
    coarse = jax.random.normal(k1, (num_classes, 8, 8, channels)) * 1.5
    return jax.image.resize(coarse, (num_classes, image_size, image_size, channels), "bilinear")


def _old_label_partition(key, num_clients, samples_per_client, num_classes, alpha):
    import jax.numpy as jnp

    k1, k2 = jax.random.split(key)
    props = jax.random.dirichlet(k1, jnp.full((num_classes,), alpha), (num_clients,))
    labels = jax.vmap(
        lambda k, p: jax.random.choice(k, num_classes, (samples_per_client,), p=p)
    )(jax.random.split(k2, num_clients), props)
    return labels.astype(jnp.int32)


def _old_generate(key, *, num_clients, samples_per_client, num_classes, image_size,
                  channels, alpha, test_size, noise):
    import jax.numpy as jnp

    kp, kl, kx, kt = jax.random.split(key, 4)
    protos = _old_prototypes(kp, num_classes, image_size, channels)
    labels = _old_label_partition(kl, num_clients, samples_per_client, num_classes, alpha)
    eps = jax.random.normal(kx, (num_clients, samples_per_client, image_size, image_size, channels))
    test_labels = (jnp.arange(test_size) % num_classes).astype(jnp.int32)
    test_eps = jax.random.normal(kt, (test_size, image_size, image_size, channels))
    return {
        "images": protos[labels] + noise * eps,
        "labels": labels,
        "test_images": protos[test_labels] + noise * test_eps,
        "test_labels": test_labels,
    }


def _old_make_dataset(seed, data_cfg, model_cfg):
    kw = dict(num_clients=data_cfg["num_clients"],
              samples_per_client=data_cfg["samples_per_client"],
              num_classes=model_cfg["num_classes"], image_size=model_cfg["image_size"],
              channels=model_cfg["in_channels"], alpha=data_cfg["alpha"],
              test_size=data_cfg["test_size"], noise=data_cfg["noise"])
    return jax.jit(lambda key: _old_generate(key, **kw))(jax.random.PRNGKey(seed))


@pytest.mark.parametrize("seed", [0, 2**31 + 5])
def test_family_dataset_is_the_old_generator(seed):
    """The CNN family's dataset is, bit for bit, the benchmark's generator
    as it stood before it moved into ``bench/models/cnn.py``."""
    ours = cnn.make_dataset(seed, tiny.DATA, tiny.MODEL)
    old = _old_make_dataset(seed, tiny.DATA, tiny.MODEL)
    assert ours.keys() == old.keys()
    for k in old:
        np.testing.assert_array_equal(np.asarray(ours[k]), np.asarray(old[k]), err_msg=k)
