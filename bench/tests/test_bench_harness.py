"""The harness finds every configuration, cell and metric by name, takes new
ones from new files alone, and refuses to run without a TPU."""
from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench import harness
from bench.tests import tiny

REPO = tiny.REPO
SPEC = json.loads((REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("workload", [w["name"] for w in SPEC["workloads"]])
def test_every_cell_loads_by_name(workload):
    cell = harness.load_cell(REPO, workload)
    assert cell.traffic["entry"] == "run_simulation"
    assert cell.traffic["horizon"] % cell.config["sim"]["eval_every"] == 0
    assert set(cell.limits["limits"]) <= {"int_mismatch", "probe_m_gap", "age_level_gap",
                                           "fedavg_gap", "param_change_gap", "f1_gap"}
    assert cell.limits["limits"]["int_mismatch"] == 0
    for trace in (False, True):
        for m in cell.metrics(trace):
            if trace:
                assert callable(harness.load_reader(cell, m["name"]))
            else:
                assert m["name"] in harness._END_TO_END


def test_configs_are_the_program_model():
    from repro.configs.cifar_cnn import CONFIG

    for c in SPEC["configs"]:
        cfg = json.loads((REPO / c["file"]).read_text())
        m = cfg["model"]
        assert (m["image_size"], m["in_channels"], m["num_classes"]) == (
            CONFIG.image_size, CONFIG.in_channels, CONFIG.num_classes)
        assert tuple(m["conv_channels"]) == CONFIG.conv_channels
        assert tuple(m["fc_dims"]) == CONFIG.fc_dims
        assert cfg["data"]["num_clients"] == cfg["sim"]["num_clients"]
        assert set(c["reduced"]) == set(cfg["reduced"])


def test_benchmark_json_shape():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs", "workloads",
                         "end_to_end", "per_layer"}
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    assert all(0.01 <= m["bound"] <= 0.25 for m in e2e.values())
    names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer") for x in SPEC[k]]
    assert len(names) == len(set(names)) and all(NAME.match(n) for n in names)
    cells = {w["name"] for w in SPEC["workloads"]}
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e and set(m.get("workloads", cells)) <= cells
    n = 24  # the most cells a later PR may bring
    assert (2 + 14 * n) * (SPEC["run_seconds"] + 60) + n * 180 + 1200 <= 43200


def test_new_cell_and_metric_come_from_new_files(tmp_path):
    """A fixture cell and a fixture metric, added as files and entries in a
    copy of the benchmark, are found and reported by a run."""
    root = tiny.make_root(tmp_path)
    (root / "bench" / "metrics" / "fixture_calls.py").write_text(
        "def read(ctx):\n    return float(ctx.n_calls)\n")
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["per_layer"].append({"name": "fixture_calls", "unit": "calls", "better": "higher",
                              "source": "host_clock", "layer": "drivers",
                              "moves": "client_epochs_per_s", "workloads": ["tiny_vaoi"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    cell = harness.load_cell(root, "tiny_vaoi")
    result = harness.run(cell, 2**31 + 99, 0.5, True, 0.0, require_chip=False)
    assert result["correct"], result["checks"]
    assert result["metrics"]["fixture_calls"]["value"] == result["attempted"] >= 1
    assert "driver_compile_s_per_call" in result["metrics"]
    assert all(m["name"] != "fixture_calls" for m in SPEC["per_layer"])  # the repo's copy is untouched


def _run(cwd: Path, env: dict) -> subprocess.CompletedProcess:
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", SPEC["workloads"][0]["name"],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_refuses_without_a_tpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = _run(REPO, env)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not p.stdout.strip()


def test_run_refuses_with_only_the_benchmark_files(tmp_path):
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".jax_cache", ".trace", "__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = _run(tmp_path, env)
    assert p.returncode != 0
    assert not p.stdout.strip()


@pytest.mark.parametrize("extra", [{"channel": "erasure", "channel_params": {"p_loss": 0.3}},
                                   {"stream": "drift"}, {"harvest_params": {"p": 0.2}}])
def test_cell_with_a_scenario_the_reference_lacks_is_refused(tmp_path, extra):
    """A traffic file that asks for a channel, stream or harvest the plain
    reference does not implement is refused before anything runs."""
    root = tiny.make_root(tmp_path, traffic_extra=extra)
    with pytest.raises(ValueError, match="the reference implements"):
        harness.sim_config(harness.load_cell(root, "tiny_vaoi"), 0, 4)
