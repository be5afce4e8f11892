"""The paper's client CNN (§V): 6 convolutions (3x3, SAME, ReLU; a 2x2
max-pool after every second), then fully connected layers and a softmax
output, on CIFAR-shaped images.  The family interface is in
``bench/models/__init__.py``.

The reference network is written from the paper in plain ``jax.numpy``; the
data generator is the benchmark's copy of the program's
``repro.data.synthetic.make_federated_dataset`` (class-conditional Gaussian
images, Dirichlet(alpha) label partition, balanced test set), kept here so
that no later change to the program can change what the benchmark feeds it.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp


# --------------------------------------------------------------------------
# The program under test
# --------------------------------------------------------------------------


def _program_config(model: dict):
    from repro.configs.cifar_cnn import CNNConfig

    return CNNConfig(
        image_size=model["image_size"], in_channels=model["in_channels"],
        num_classes=model["num_classes"], conv_channels=tuple(model["conv_channels"]),
        fc_dims=tuple(model["fc_dims"]),
    )


def program_backend(model: dict, frozen=None):
    from repro.fl import cnn_backend

    return cnn_backend(_program_config(model))


def check_model(model: dict) -> None:
    """The configuration is the program's own §V model,
    ``repro.configs.cifar_cnn.CONFIG``."""
    from repro.configs.cifar_cnn import CONFIG

    if _program_config(model) != CONFIG:
        raise ValueError(f"not the program's CNN {CONFIG}: {model}")


def to_reference(params: dict) -> dict:
    return params


# --------------------------------------------------------------------------
# The reference network
# --------------------------------------------------------------------------


def init_params(model: dict, key: jax.Array) -> Dict[str, jax.Array]:
    convs, fcs = model["conv_channels"], model["fc_dims"]
    ks = jax.random.split(key, len(convs) + len(fcs) + 1)
    p, cin = {}, model["in_channels"]
    for i, cout in enumerate(convs):
        p[f"conv{i}_w"] = jax.random.normal(ks[i], (3, 3, cin, cout), jnp.float32) * (
            1.0 / jnp.sqrt(9 * cin)
        )
        p[f"conv{i}_b"] = jnp.zeros((cout,), jnp.float32)
        cin = cout
    side = model["image_size"] // 8
    dims = [side * side * convs[-1], *fcs, model["num_classes"]]
    for i in range(len(dims) - 1):
        w = jax.random.normal(ks[len(convs) + i], (dims[i], dims[i + 1]), jnp.float32)
        p[f"fc{i}_w"] = w / jnp.sqrt(dims[i])
        p[f"fc{i}_b"] = jnp.zeros((dims[i + 1],), jnp.float32)
    return p


def logits(model: dict, num, p: dict, x: jax.Array) -> jax.Array:
    x = x.astype(num.dtype)
    for i in range(len(model["conv_channels"])):
        x = jax.lax.conv_general_dilated(
            x, p[f"conv{i}_w"].astype(num.dtype), (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=num.precision,
        )
        x = jnp.maximum(x + p[f"conv{i}_b"].astype(num.dtype), 0)
        if i % 2 == 1:
            x = jax.lax.reduce_window(
                x, -jnp.inf, jax.lax.max, (1, 2, 2, 1), (1, 2, 2, 1), "VALID"
            )
    x = x.reshape(x.shape[0], -1)
    n_fc = len(model["fc_dims"]) + 1
    for i in range(n_fc):
        x = jnp.dot(x, p[f"fc{i}_w"].astype(num.dtype), precision=num.precision)
        x = x + p[f"fc{i}_b"].astype(num.dtype)
        if i < n_fc - 1:
            x = jnp.maximum(x, 0)
    return x


def _softmax_mean(z: jax.Array) -> jax.Array:
    z = z - jnp.max(z, axis=-1, keepdims=True)
    e = jnp.exp(z)
    return jnp.mean(e / jnp.sum(e, axis=-1, keepdims=True), axis=0)


def _loss(model, num, p, x, y):
    z = logits(model, num, p, x)
    z = z - jnp.max(z, axis=-1, keepdims=True)
    logz = jnp.log(jnp.sum(jnp.exp(z), axis=-1))
    gold = jnp.take_along_axis(z, y[:, None], axis=-1)[:, 0]
    return jnp.mean(logz - gold)


class Model:
    """Jitted pieces of the reference model."""

    def __init__(self, model: dict, num, lr: float):
        self.feature_dim = model["num_classes"]
        self.init = lambda key: init_params(model, key)
        self.predict = jax.jit(lambda p, x: jnp.argmax(logits(model, num, p, x), axis=-1))

        def probe(p, x):
            # every client's probe batch through one forward pass (the batch
            # axis of the model), then each client's mean softmax
            n, b = x.shape[:2]
            z = logits(model, num, p, x.reshape((n * b,) + x.shape[2:]))
            z = z - jnp.max(z, axis=-1, keepdims=True)
            e = jnp.exp(z)
            return jnp.mean((e / jnp.sum(e, axis=-1, keepdims=True)).reshape(n, b, -1), axis=1)

        self.probe = jax.jit(probe)

        def sgd_step(p, fsum, xs, ys, step):
            x, y = xs[step], ys[step]
            g = jax.grad(lambda q: _loss(model, num, q, x, y))(p)
            p = {k: (p[k] - jnp.asarray(lr, num.dtype) * g[k]).astype(num.dtype) for k in p}
            f = _softmax_mean(logits(model, num, p, x)).astype(jnp.float32)
            return p, fsum + f * x.shape[0]

        self.sgd_step = jax.jit(sgd_step)


def reference_model(model: dict, numerics, lr: float, frozen=None) -> Model:
    return Model(model, numerics, lr)


# --------------------------------------------------------------------------
# The data
# --------------------------------------------------------------------------


def num_classes(model: dict) -> int:
    return model["num_classes"]


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


def make_dataset(seed: int, data: dict, model: dict, sharding=None) -> Dict[str, jax.Array]:
    """The federated dataset of one configuration, made from ``seed`` in one
    jitted call.  ``sharding``: a ``NamedSharding`` for the client axis of
    ``images``/``labels`` (the test set is replicated), or ``None`` for the
    default device.  JAX's partitionable threefry makes the values
    independent of the sharding, so a fleet's data never has to fit on one
    chip."""
    kw = dict(
        num_clients=data["num_clients"],
        samples_per_client=data["samples_per_client"],
        num_classes=model["num_classes"],
        image_size=model["image_size"],
        channels=model["in_channels"],
        alpha=data["alpha"],
        test_size=data["test_size"],
        noise=data["noise"],
    )
    out_shardings = None
    if sharding is not None:
        rep = jax.sharding.NamedSharding(sharding.mesh, jax.sharding.PartitionSpec())
        out_shardings = {"images": sharding, "labels": sharding,
                         "test_images": rep, "test_labels": rep}
    gen = jax.jit(lambda key: _generate(key, **kw), out_shardings=out_shardings)
    return gen(jax.random.PRNGKey(seed))


# --------------------------------------------------------------------------
# Operation counts: only the multiply-adds of convolutions and dense layers
# count (two operations each); biases, ReLUs, pooling and softmax are left
# out, as is usual for a model-FLOPs utilization.
# --------------------------------------------------------------------------


def forward_flops(model: dict) -> int:
    """Operations of one image's forward pass: 3x3 SAME convolutions, a 2x2
    max-pool after every second one, then the dense layers."""
    side, cin, total = model["image_size"], model["in_channels"], 0
    for i, cout in enumerate(model["conv_channels"]):
        total += 2 * side * side * 9 * cin * cout
        cin = cout
        if i % 2 == 1:
            side //= 2
    dims = [side * side * cin, *model["fc_dims"], model["num_classes"]]
    return total + sum(2 * a * b for a, b in zip(dims, dims[1:]))


def feature_flops(model: dict) -> int:
    """The Eq. 5/6 feature is the mean softmax output: a full forward."""
    return forward_flops(model)


def train_flops(model: dict) -> int:
    """Operations of one image's SGD step: forward plus backward, the
    backward counted as twice the forward (input and weight gradients)."""
    return 3 * forward_flops(model)
