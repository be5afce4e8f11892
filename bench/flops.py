"""Useful operations of a window, from the configuration's shapes and the
family's per-item operation counts (``bench/models/<family>.py``)."""
from __future__ import annotations

from types import ModuleType


def window_flops(family: ModuleType, model: dict, sim: dict, data: dict, policy: str, *,
                 n_started: int, epochs: int, evals: int) -> float:
    """Useful operations of a window: local training of the clients that
    started (kappa steps of one batch each), the per-step feature forward
    and the per-epoch probe forward of every client under a VAoI policy,
    and the eval forwards.  Padding lanes of a training slab, and the
    discarded training of clients that did not start on the dense path, do
    not count."""
    feat = family.feature_flops(model)
    kappa = sim["kappa"]
    batch = max(1, data["samples_per_client"] // kappa)
    flops = n_started * kappa * batch * family.train_flops(model)
    if policy.startswith("vaoi"):
        flops += n_started * kappa * batch * feat
        flops += epochs * sim["num_clients"] * sim["probe_size"] * feat
    return float(flops + evals * data["test_size"] * family.forward_flops(model))
