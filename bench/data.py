"""The benchmark's copy of the simulator's synthetic data generator.

A copy of ``repro.data.synthetic.make_federated_dataset`` (class-conditional
Gaussian 32 px images, Dirichlet(alpha) label partition, balanced test set),
kept here so that no later change to the program can change what the
benchmark feeds it.  ``make_dataset`` runs the whole generator as one jitted
call on the device; given a ``sharding`` for the client axis it writes each
client's rows straight onto its device (JAX's partitionable threefry makes
the values independent of the sharding), so a fleet's data never has to fit
on one chip.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp


def _prototypes(key, num_classes, image_size, channels):
    k1, _ = jax.random.split(key)
    coarse = jax.random.normal(k1, (num_classes, 8, 8, channels)) * 1.5
    return jax.image.resize(coarse, (num_classes, image_size, image_size, channels), "bilinear")


def _label_partition(key, num_clients, samples_per_client, num_classes, alpha):
    k1, k2 = jax.random.split(key)
    props = jax.random.dirichlet(k1, jnp.full((num_classes,), alpha), (num_clients,))
    labels = jax.vmap(
        lambda k, p: jax.random.choice(k, num_classes, (samples_per_client,), p=p)
    )(jax.random.split(k2, num_clients), props)
    return labels.astype(jnp.int32)


def _generate(key, *, num_clients, samples_per_client, num_classes, image_size,
              channels, alpha, test_size, noise):
    kp, kl, kx, kt = jax.random.split(key, 4)
    protos = _prototypes(kp, num_classes, image_size, channels)
    labels = _label_partition(kl, num_clients, samples_per_client, num_classes, alpha)
    eps = jax.random.normal(kx, (num_clients, samples_per_client, image_size, image_size, channels))
    test_labels = (jnp.arange(test_size) % num_classes).astype(jnp.int32)
    test_eps = jax.random.normal(kt, (test_size, image_size, image_size, channels))
    return {
        "images": protos[labels] + noise * eps,
        "labels": labels,
        "test_images": protos[test_labels] + noise * test_eps,
        "test_labels": test_labels,
    }


def make_dataset(seed: int, data_cfg: dict, model_cfg: dict, sharding=None) -> Dict[str, jax.Array]:
    """The federated dataset of one configuration, made from ``seed`` in one
    jitted call.  ``sharding``: a ``NamedSharding`` for the client axis of
    ``images``/``labels`` (the test set is replicated), or ``None`` for the
    default device."""
    kw = dict(
        num_clients=data_cfg["num_clients"],
        samples_per_client=data_cfg["samples_per_client"],
        num_classes=model_cfg["num_classes"],
        image_size=model_cfg["image_size"],
        channels=model_cfg["in_channels"],
        alpha=data_cfg["alpha"],
        test_size=data_cfg["test_size"],
        noise=data_cfg["noise"],
    )
    out_shardings = None
    if sharding is not None:
        rep = jax.sharding.NamedSharding(sharding.mesh, jax.sharding.PartitionSpec())
        out_shardings = {"images": sharding, "labels": sharding,
                         "test_images": rep, "test_labels": rep}
    gen = jax.jit(lambda key: _generate(key, **kw), out_shardings=out_shardings)
    return gen(jax.random.PRNGKey(seed))
