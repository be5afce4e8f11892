"""A second client model family, for the tests only: a two-layer MLP whose
first layer is frozen (made by ``make_frozen``, shared by the program and
the reference) under a trainable softmax head, on class-conditional
Gaussian feature vectors.  ``tiny.make_root(model=tiny.MLP)`` copies this file
into a temporary root as ``bench/models/mlp.py``, beside the benchmark's own
files: the harness finds it by the configuration's ``model.family`` alone.

The "program" is a backend of the simulator written here: its message
carries the frozen layer beside the head (``{"trunk": ..., "head": ...}``,
the trunk under ``stop_gradient``), so that ``to_reference`` has a nested
tree to flatten and ``frozen_gap`` has the program's copy of the frozen
weights to hold to the reference's.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

NUMBERS = ("frozen_gap",)
TRUNK = ("trunk_w", "trunk_b")
HEAD = ("head_w", "head_b")


def make_frozen(seed: int, model: dict) -> dict:
    d, h = model["in_dim"], model["hidden"]

    @jax.jit
    def make(key):
        kw, kb = jax.random.split(jax.random.fold_in(key, 1))
        return {"w": jax.random.normal(kw, (d, h)) / jnp.sqrt(d),
                "b": 0.1 * jax.random.normal(kb, (h,))}

    return make(jax.random.PRNGKey(seed))


def _head_init(model: dict, key: jax.Array) -> dict:
    h, c = model["hidden"], model["num_classes"]
    return {"w": jax.random.normal(key, (h, c), jnp.float32) / jnp.sqrt(h),
            "b": jnp.zeros((c,), jnp.float32)}


def program_backend(model: dict, frozen: dict):
    from repro.core.simulator import Backend

    c = model["num_classes"]

    def logits(p, x):
        t = jax.lax.stop_gradient(p["trunk"])
        return jnp.maximum(x @ t["w"] + t["b"], 0) @ p["head"]["w"] + p["head"]["b"]

    def loss(p, x, y):
        return -jnp.mean(jnp.take_along_axis(jax.nn.log_softmax(logits(p, x)), y[:, None], 1))

    return Backend(
        # a copy: the simulator donates its carry, which would delete the
        # frozen arrays themselves
        init=lambda key: {"trunk": jax.tree.map(jnp.copy, frozen),
                          "head": _head_init(model, key)},
        grad_loss=jax.value_and_grad(loss),
        feature=lambda p, x: jnp.mean(jax.nn.softmax(logits(p, x)), axis=0),
        predict=lambda p, x: jnp.argmax(logits(p, x), axis=-1),
        feature_dim=c,
        num_classes=c,
    )


def to_reference(params: dict) -> dict:
    return {f"{part}_{leaf}": v for part, leaves in params.items() for leaf, v in leaves.items()}


class _Reference:
    def __init__(self, model: dict, num, lr: float, frozen: dict):
        self.feature_dim = model["num_classes"]
        dt, prec = num.dtype, num.precision
        self.init = lambda key: {"trunk_w": frozen["w"], "trunk_b": frozen["b"],
                                 **{f"head_{k}": v for k, v in _head_init(model, key).items()}}

        def logits(p, x):
            h = jnp.dot(x.astype(dt), p["trunk_w"].astype(dt), precision=prec)
            h = jnp.maximum(h + p["trunk_b"].astype(dt), 0)
            return jnp.dot(h, p["head_w"].astype(dt), precision=prec) + p["head_b"].astype(dt)

        def softmax(z):
            e = jnp.exp(z - jnp.max(z, axis=-1, keepdims=True))
            return e / jnp.sum(e, axis=-1, keepdims=True)

        self.predict = jax.jit(lambda p, x: jnp.argmax(logits(p, x), axis=-1))
        self.probe = jax.jit(lambda p, xs: jnp.mean(
            softmax(logits(p, xs.reshape((-1, xs.shape[-1])))).reshape(xs.shape[:2] + (-1,)),
            axis=1))

        def sgd_step(p, fsum, xs, ys, step):
            x, y = xs[step], ys[step]

            def loss(head):
                z = logits({**p, **head}, x)
                z = z - jnp.max(z, axis=-1, keepdims=True)
                gold = jnp.take_along_axis(z, y[:, None], axis=-1)[:, 0]
                return jnp.mean(jnp.log(jnp.sum(jnp.exp(z), axis=-1)) - gold)

            g = jax.grad(loss)({k: p[k] for k in HEAD})
            p = {**p, **{k: (p[k] - jnp.asarray(lr, dt) * g[k]).astype(dt) for k in HEAD}}
            f = jnp.mean(softmax(logits(p, x)), axis=0).astype(jnp.float32)
            return p, fsum + f * x.shape[0]

        self.sgd_step = jax.jit(sgd_step)


def reference_model(model: dict, numerics, lr: float, frozen: dict) -> _Reference:
    return _Reference(model, numerics, lr, frozen)


def numbers(prog: dict, ref: dict, data: dict) -> dict:
    """``frozen_gap``: by the worst leaf, how far the program's global model
    holds the frozen layer from the reference's, over the reference's norm."""
    got, want = prog["global_params"], ref["global_params"]
    gap = lambda k: float(np.linalg.norm(np.asarray(got[k], np.float64) - want[k])
                          / np.linalg.norm(want[k]))
    return {"frozen_gap": max(gap(k) for k in TRUNK)}


def num_classes(model: dict) -> int:
    return model["num_classes"]


def make_dataset(seed: int, data: dict, model: dict, sharding=None) -> dict:
    n, s, c, d = (data["num_clients"], data["samples_per_client"], model["num_classes"],
                  model["in_dim"])

    @jax.jit
    def gen(key):
        kp, kd, kl, kx, kt = jax.random.split(key, 5)
        protos = 1.5 * jax.random.normal(kp, (c, d))
        props = jax.random.dirichlet(kd, jnp.full((c,), data["alpha"]), (n,))
        labels = jax.vmap(lambda k, p: jax.random.choice(k, c, (s,), p=p))(
            jax.random.split(kl, n), props).astype(jnp.int32)
        test_labels = (jnp.arange(data["test_size"]) % c).astype(jnp.int32)
        return {
            "images": protos[labels] + data["noise"] * jax.random.normal(kx, (n, s, d)),
            "labels": labels,
            "test_images": protos[test_labels]
            + data["noise"] * jax.random.normal(kt, (data["test_size"], d)),
            "test_labels": test_labels,
        }

    return gen(jax.random.PRNGKey(seed))


def forward_flops(model: dict) -> int:
    return 2 * model["hidden"] * (model["in_dim"] + model["num_classes"])


def feature_flops(model: dict) -> int:
    return forward_flops(model)


def train_flops(model: dict) -> int:
    """The frozen layer's forward, and the head's forward and backward."""
    return 2 * model["in_dim"] * model["hidden"] + 6 * model["hidden"] * model["num_classes"]
