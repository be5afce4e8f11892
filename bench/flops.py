"""Operation counts of the client CNN, from the configuration's shapes.

Only the multiply-adds of convolutions and dense layers count (two
operations each); biases, ReLUs, pooling and softmax are left out, as is
usual for a model-FLOPs utilization.
"""
from __future__ import annotations


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


def train_flops(model: dict) -> int:
    """Operations of one image's SGD step: forward plus backward, the
    backward counted as twice the forward (input and weight gradients)."""
    return 3 * forward_flops(model)


def window_flops(model: dict, sim: dict, data: dict, policy: str, *, n_started: int,
                 epochs: int, evals: int) -> float:
    """Useful operations of a window: local training of the clients that
    started (kappa steps of one batch each), the per-step feature forward
    and the per-epoch probe forward of every client under a VAoI policy,
    and the eval forwards.  Padding lanes of a training slab, and the
    discarded training of clients that did not start on the dense path, do
    not count."""
    fwd = forward_flops(model)
    kappa = sim["kappa"]
    batch = max(1, data["samples_per_client"] // kappa)
    flops = n_started * kappa * batch * train_flops(model)
    if policy.startswith("vaoi"):
        flops += n_started * kappa * batch * fwd
        flops += epochs * sim["num_clients"] * sim["probe_size"] * fwd
    return float(flops + evals * data["test_size"] * fwd)
