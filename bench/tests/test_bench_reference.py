"""The plain reference against the simulator's epoch body, on the CPU at a
tiny size, and the benchmark's data generator against the program's.

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
from bench.data import make_dataset
from bench.tests import tiny

EPOCHS = 12


def _program(policy, compact, seed, data):
    from repro.configs.cifar_cnn import CNNConfig
    from repro.core import EHFLConfig, run_simulation
    from repro.fl import cnn_backend

    m = tiny.MODEL
    be = cnn_backend(CNNConfig(image_size=m["image_size"], conv_channels=tuple(m["conv_channels"]),
                               fc_dims=tuple(m["fc_dims"])))
    s = dict(tiny.SIM)
    cfg = EHFLConfig(**s, epochs=EPOCHS, policy=policy, compact=compact, seed=seed)
    return run_simulation(cfg, be, data)


@pytest.mark.parametrize("policy,compact", [
    ("vaoi", "auto"), ("vaoi", False), ("fedavg", "auto"), ("fedavg", False),
])
def test_reference_matches_epoch_body(policy, compact):
    seed = 5
    data = make_dataset(3, tiny.DATA, tiny.MODEL)
    out = _program(policy, compact, seed, data)
    pm = jax.tree.map(np.asarray, out["metrics"])
    ref = reference.simulate(tiny.SIM, tiny.MODEL, data, seed, EPOCHS, policy)
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
    data = make_dataset(3, tiny.DATA, tiny.MODEL)
    base = reference.simulate(tiny.SIM, tiny.MODEL, data, 5, 6, "vaoi")
    sums = base["metrics"]["age_sum"]
    first = int(np.flatnonzero(base["metrics"]["n_started"])[0])
    held = reference.simulate(tiny.SIM, tiny.MODEL, data, 5, 6, "vaoi", witness=sums, margin=1e-6)
    assert held["t_stop"] == first + 1 and held["witness_faults"] == 0
    # every free client ambiguous, and a sum (1 of the 5 at epoch 0, when all
    # ages are 0) that no common decision gives: the lockstep ends, no fault
    split = sums.copy()
    split[0] = 1
    wide = reference.simulate(tiny.SIM, tiny.MODEL, data, 5, 6, "vaoi", witness=split, margin=10.0)
    assert wide["t_stop"] == 0 and wide["witness_faults"] == 0
    bad = sums.copy()
    bad[first] += 100
    off = reference.simulate(tiny.SIM, tiny.MODEL, data, 5, 6, "vaoi", witness=bad, margin=1e-6)
    assert off["witness_faults"] == 1 and off["t_stop"] == first


def test_control_fails_the_comparison():
    """The reference in bfloat16, in the program's place, reads over the
    tiny cell's limits."""
    data = make_dataset(4, tiny.DATA, tiny.MODEL)
    n = tiny.SIM["num_clients"]
    c = reference.simulate(tiny.SIM, tiny.MODEL, data, 4, EPOCHS, "vaoi", numerics=reference.CONTROL)
    got = {"metrics": {**c["metrics"], "n_delivered": c["metrics"]["n_uploaded"], "f1": c["f1"]},
           "global_params": c["global_params"], "msg_params": c["msg_params"],
            "final": c["final"]}
    ref = reference.simulate(tiny.SIM, tiny.MODEL, data, 4, EPOCHS, "vaoi",
                             witness=compare.age_sums(c["metrics"]["avg_age"], n), margin=1e-4)
    nums = compare.numbers(got, ref, reference.eval_f1(tiny.MODEL, c["global_params"], data), n)
    ok, _ = compare.judge(nums, {"int_mismatch": 0, "probe_m_gap": 1e-6, "param_change_gap": 1e-4})
    assert not ok, nums


def test_data_copy_matches_the_program_generator():
    """Labels equal; images to one float32 rounding (the copy runs as one
    jitted call, where ``proto + noise * eps`` may fuse into one
    multiply-add, while the program's generator runs op by op)."""
    from repro.data import make_federated_dataset

    d = tiny.DATA
    ours = make_dataset(11, d, tiny.MODEL)
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
    sharded = make_dataset(9, data_cfg, tiny.MODEL, sharding=NamedSharding(mesh, PartitionSpec("data")))
    plain = make_dataset(9, data_cfg, tiny.MODEL)
    assert len(sharded["images"].sharding.device_set) == n
    for k in plain:
        np.testing.assert_array_equal(np.asarray(sharded[k]), np.asarray(plain[k]), err_msg=k)
