"""Faults planted in the program under test, each where its layer produces
its result, to show that ``correct`` reads them.  Each takes a
``patch(obj, name, value)`` that replaces an attribute until it is undone
(pytest's ``monkeypatch.setattr``, or :class:`Patch`); the program must be
traced after the patch, as every ``run_simulation`` call is."""
from __future__ import annotations

from typing import Any, Callable, List, Tuple

import jax.numpy as jnp


def unchanged_training(patch: Callable) -> None:
    """Local training returns the model it was given."""
    from repro.core import simulator

    def no_step(params, images, labels, key, cfg, backend, with_feature=True):
        f = jnp.zeros((backend.feature_dim,), jnp.float32) if with_feature else None
        return params, f

    patch(simulator, "_local_train", no_step)


def half_the_uploads(patch: Callable) -> None:
    """FedAvg leaves out every other client (dense) or slab lane (compacted)
    and averages the rest."""
    from repro.core import simulator

    dense, compact = simulator._masked_mean, simulator._compact_mean

    def half_dense(stacked, mask, fallback, reduce_sum=None):
        keep = jnp.arange(mask.shape[0]) % 2 == 0
        return dense(stacked, mask & keep, fallback, reduce_sum)

    def half_compact(slab, slab_mask, old, old_mask, fallback, reduce_sum=None, use_kernel=False):
        keep = jnp.arange(slab_mask.shape[0]) % 2 == 0
        return compact(slab, slab_mask & keep, old, old_mask, fallback, reduce_sum, use_kernel)

    patch(simulator, "_masked_mean", half_dense)
    patch(simulator, "_compact_mean", half_compact)


def half_the_batch(patch: Callable) -> None:
    """Each local SGD step takes its gradient over the first half of its
    minibatch: the mean over the rest."""
    from bench import harness

    orig = harness.backend

    def backend(cell, frozen=None):
        be = orig(cell, frozen)
        grad = be.grad_loss
        return be._replace(grad_loss=lambda p, x, y: grad(p, x[: x.shape[0] // 2],
                                                         y[: y.shape[0] // 2]))

    patch(harness, "backend", backend)


def altered_age(patch: Callable) -> None:
    """One client's VAoI age is off by one where Eq. 7 produces it."""
    from repro.core import vaoi

    orig = vaoi.vaoi_update
    patch(vaoi, "vaoi_update", lambda *a: orig(*a).at[0].add(1.0))


def inverted_selection(patch: Callable) -> None:
    """Alg. 2's top-k favours the freshest clients instead of the stalest."""
    from repro.core import vaoi

    orig = vaoi.select_topk
    patch(vaoi, "select_topk", lambda age, k, key: orig(jnp.max(age) - age, k, key))


def probe_half_images(patch: Callable) -> None:
    """The Eq. 5 probe forward reads only the first half of each client's
    probe images (the program is handed half the probe size; the reference
    keeps the configuration's)."""
    import dataclasses

    from bench import harness

    orig = harness.sim_config
    patch(harness, "sim_config", lambda cell, seed, epochs: dataclasses.replace(
        orig(cell, seed, epochs), probe_size=cell.config["sim"]["probe_size"] // 2))


def probe_neighbour_images(patch: Callable) -> None:
    """Each client's Eq. 5 distance reads its neighbour's probe features."""
    from repro.core import vaoi

    orig = vaoi.feature_distance
    patch(vaoi, "feature_distance", lambda v, h: orig(jnp.roll(v, 1, axis=0), h))


def altered_prediction(patch: Callable) -> None:
    """The eval's predictions are altered where they are produced: each is
    the next class (the last one none)."""
    from repro.core import simulator

    orig = simulator._jitted_predict
    simulator._jitted_predict.cache_clear()
    patch(simulator, "_jitted_predict", lambda predict: (lambda p, x: orig(predict)(p, x) + 1))


FAULTS = {f.__name__: f for f in (unchanged_training, half_the_uploads, half_the_batch,
                                  altered_age, inverted_selection, probe_half_images,
                                  probe_neighbour_images, altered_prediction)}


class Patch:
    """``patch(obj, name, value)`` outside pytest; ``undo()`` restores."""

    def __init__(self):
        self.saved: List[Tuple[Any, str, Any]] = []

    def __call__(self, obj: Any, name: str, value: Any) -> None:
        self.saved.append((obj, name, getattr(obj, name)))
        setattr(obj, name, value)

    def undo(self) -> None:
        for obj, name, value in reversed(self.saved):
            setattr(obj, name, value)
        self.saved.clear()
        from repro.core import simulator

        simulator._jitted_predict.cache_clear()
