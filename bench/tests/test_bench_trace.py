"""Trace reduction, operation counts and the table of peaks."""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np
import pytest

from bench import flops, peaks
from bench import trace as tr
from bench.models import cnn
from bench.tests import tiny

FIXTURE = Path(__file__).with_name("data") / "run_simulation_small.xplane.pb.gz"
PAPER = json.loads((tiny.REPO / "bench" / "configs" / "cnn_paper_n100.json").read_text())


def test_forward_flops_hand_count():
    """cifar_cnn.CONFIG at 32 px, by hand: 2 * H * W * 9 * cin * cout per
    conv (a 2x2 pool after every second), 2 * din * dout per dense layer."""
    convs = [2 * 32 * 32 * 9 * 3 * 32, 2 * 32 * 32 * 9 * 32 * 32,
             2 * 16 * 16 * 9 * 32 * 64, 2 * 16 * 16 * 9 * 64 * 64,
             2 * 8 * 8 * 9 * 64 * 128, 2 * 8 * 8 * 9 * 128 * 128]
    dense = [2 * 4 * 4 * 128 * 256, 2 * 256 * 128, 2 * 128 * 10]
    assert cnn.forward_flops(PAPER["model"]) == sum(convs) + sum(dense) == 78_383_616
    assert cnn.feature_flops(PAPER["model"]) == 78_383_616
    assert cnn.train_flops(PAPER["model"]) == 3 * 78_383_616


def test_window_flops_counts_only_started_clients():
    m, s, d = PAPER["model"], PAPER["sim"], PAPER["data"]
    fwd = cnn.forward_flops(m)
    batch = d["samples_per_client"] // s["kappa"]
    got = flops.window_flops(cnn, m, s, d, "vaoi", n_started=7, epochs=3, evals=2)
    want = (7 * s["kappa"] * batch * 4 * fwd + 3 * s["num_clients"] * s["probe_size"] * fwd
            + 2 * d["test_size"] * fwd)
    assert got == want
    assert flops.window_flops(cnn, m, s, d, "fedavg", n_started=7, epochs=3, evals=0) == (
        7 * s["kappa"] * batch * 3 * fwd)


def _old_forward_flops(model):
    """``bench/flops.py``'s count as it stood before the model families."""
    side, cin, total = model["image_size"], model["in_channels"], 0
    for i, cout in enumerate(model["conv_channels"]):
        total += 2 * side * side * 9 * cin * cout
        cin = cout
        if i % 2 == 1:
            side //= 2
    dims = [side * side * cin, *model["fc_dims"], model["num_classes"]]
    return total + sum(2 * a * b for a, b in zip(dims, dims[1:]))


def _old_window_flops(model, sim, data, policy, *, n_started, epochs, evals):
    fwd = _old_forward_flops(model)
    kappa = sim["kappa"]
    batch = max(1, data["samples_per_client"] // kappa)
    flops = n_started * kappa * batch * 3 * fwd
    if policy.startswith("vaoi"):
        flops += n_started * kappa * batch * fwd
        flops += epochs * sim["num_clients"] * sim["probe_size"] * fwd
    return float(flops + evals * data["test_size"] * fwd)


@pytest.mark.parametrize("policy", ["vaoi", "fedavg"])
def test_window_flops_is_the_old_count(policy):
    """``cnn_paper_n100``'s window count through the CNN family equals the
    count as it was computed before the families, for both policies."""
    m, s, d = PAPER["model"], PAPER["sim"], PAPER["data"]
    kw = dict(n_started=1234, epochs=900, evals=90)
    assert flops.window_flops(cnn, m, s, d, policy, **kw) == _old_window_flops(m, s, d, policy,
                                                                                **kw)


def test_peaks_known_and_unknown_device():
    assert peaks.peak("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(peaks.UnknownDevice):
        peaks.peak("TPU v9 imaginary")


def test_union_length():
    assert tr.union_length([(0, 2), (1, 3), (5, 6)], 0, 10) == 4
    assert tr.union_length([(0, 2), (1, 3), (5, 6)], 1.5, 5.5) == 2
    assert tr.union_length([], 0, 1) == 0


@pytest.fixture(scope="module")
def chip_trace():
    """Two calls of ``run_simulation`` at 10 clients, traced on a TPU v5e
    with the benchmark's spans (``bench/calibrate.py --trace-out``)."""
    return tr.load(str(FIXTURE))


def test_chip_trace_window_and_busy(chip_trace):
    t = chip_trace
    assert list(t.ops) == ["/device:TPU:0"]
    assert t.window_s == pytest.approx(3.337939924)
    busy = tr.busy_s(t)["/device:TPU:0"]
    # the same union by a boolean timeline at 1 us steps
    lo, hi = t.window
    steps = np.zeros(int((hi - lo) * 1e6) + 1, bool)
    for _, s, e in t.ops["/device:TPU:0"]:
        a, b = max(s, lo), min(e, hi)
        if b > a:
            steps[int(round((a - lo) * 1e6)):int(round((b - lo) * 1e6))] = True
    assert busy == pytest.approx(steps.sum() * 1e-6, abs=2e-4)
    assert 0 < busy < t.window_s


def test_chip_trace_modules_and_gaps(chip_trace):
    t = chip_trace
    busy = tr.busy_s(t)["/device:TPU:0"]
    chunk = tr.module_s(t, "^jit_chunk")
    predict = tr.module_s(t, "^jit__lambda")
    assert 0 < predict < chunk <= busy
    assert tr.module_s(t, "^no_such_module") == 0.0
    assert tr.top_ops(t)[0][0].startswith("while.")
    gaps = tr.idle_gaps(t)
    assert sum(g for _, g in gaps) <= t.window_s - busy + 1e-9
    # the two longest gaps are the per-call retrace of run_simulation's chunk
    assert [name for name, _ in gaps[:2]] == ["trace_to_jaxpr_dynamic"] * 2
