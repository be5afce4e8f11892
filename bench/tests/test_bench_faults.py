"""A run of the harness, past its look for a chip, with the timed path
broken underneath (``bench/faults.py``): ``correct`` comes out false for
each fault and true for the program as it is.

Each fault is held to the committed limits of a cell where those limits
catch it at this tiny size.  The others are held to the tiny cell's own
limits here; at the cells' own size they were read on the chip (PERF.md):
the tiny model's F1 is near 0 whatever it predicts, its distances sit above
``mu`` from the start, so neither eval nor the probe can move much, and a
gradient over half of each minibatch moves the model's change by less than
the committed ``param_change_gap`` limit."""
from __future__ import annotations

import pytest

from bench import faults, harness
from bench.tests import tiny

# fault -> the committed cell whose limits it is held to (None: the tiny's)
VAOI = {
    "none": "paper_vaoi",
    "unchanged_training": "paper_vaoi",
    "half_the_uploads": "paper_vaoi",
    "altered_age": "paper_vaoi",
    "inverted_selection": "paper_vaoi",
    "probe_half_images": None,
    "altered_prediction": None,
}
FEDAVG = {
    "none": "paper_fedavg_dense",
    "unchanged_training": "paper_fedavg_dense",
    "half_the_uploads": "paper_fedavg_dense",
    "half_the_batch": None,
}


def _run(tmp_path, monkeypatch, fault, policy, limits_of):
    if fault != "none":
        faults.FAULTS[fault](monkeypatch.setattr)
    compact = "auto" if policy == "vaoi" else False
    root = tiny.make_root(tmp_path, policy=policy, compact=compact, limits_of=limits_of)
    return harness.run(harness.load_cell(root, f"tiny_{policy}"), 12345, 0.1, False, 0.0,
                       require_chip=False)


@pytest.mark.parametrize("fault", list(VAOI))
def test_fault_reads_incorrect(fault, tmp_path, monkeypatch):
    result = _run(tmp_path, monkeypatch, fault, "vaoi", VAOI[fault])
    assert result["correct"] is (fault == "none"), result["checks"]


@pytest.mark.parametrize("fault", list(FEDAVG))
def test_fault_reads_incorrect_dense_fedavg(fault, tmp_path, monkeypatch):
    result = _run(tmp_path, monkeypatch, fault, "fedavg", FEDAVG[fault])
    assert result["correct"] is (fault == "none"), result["checks"]
