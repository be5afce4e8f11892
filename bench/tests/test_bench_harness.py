"""The harness finds every configuration, model family, cell and metric by
name, takes new ones from new files alone, and refuses to run without a
TPU."""
from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench import compare, harness, models
from bench.tests import tiny

REPO = tiny.REPO
SPEC = json.loads((REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("workload", [w["name"] for w in SPEC["workloads"]])
def test_every_cell_loads_by_name(workload):
    cell = harness.load_cell(REPO, workload)
    assert cell.traffic["entry"] == "run_simulation"
    assert cell.traffic["horizon"] % cell.config["sim"]["eval_every"] == 0
    assert set(cell.limits["limits"]) <= set(compare.NUMBERS) | set(
        getattr(cell.family, "NUMBERS", ()))
    assert cell.limits["limits"]["int_mismatch"] == 0
    for trace in (False, True):
        for m in cell.metrics(trace):
            if trace:
                assert callable(harness.load_reader(cell, m["name"]))
            else:
                assert m["name"] in harness._END_TO_END


def test_configs_are_the_program_model():
    """Each configuration is the model its family's program builds, by the
    family's own check (the CNN: ``cifar_cnn.CONFIG``)."""
    for c in SPEC["configs"]:
        cfg = json.loads((REPO / c["file"]).read_text())
        family = models.load(REPO / "bench", cfg["model"])
        if hasattr(family, "check_model"):
            family.check_model(cfg["model"])
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


@pytest.mark.parametrize("family,error", [
    (None, "set model.family to the name of a module in bench/models/"),
    ("transformer", "bench/models/transformer.py is missing"),
])
def test_config_without_a_family_module_is_refused(tmp_path, family, error):
    """A configuration that names no family, or one with no module, fails
    when its cell is loaded, naming where the module belongs."""
    model = {k: v for k, v in tiny.MODEL.items() if k != "family"}
    if family:
        model["family"] = family
    root = tiny.make_root(tmp_path)
    (root / "bench" / "configs" / "tiny.json").write_text(json.dumps(
        {"name": "tiny", "model": model, "data": tiny.DATA, "sim": tiny.SIM}))
    with pytest.raises((ValueError, FileNotFoundError), match=re.escape(error)):
        harness.load_cell(root, "tiny_vaoi")


@pytest.mark.parametrize("policy,compact", [("vaoi", "auto"), ("fedavg", False)])
def test_new_family_comes_from_a_new_file(tmp_path, policy, compact):
    """A second model family, added to a copy of the benchmark as one new
    file under ``bench/models/`` with a configuration, traffic and limits of
    its own, runs its cell through the harness with ``correct`` true; the
    number it adds to the comparison is held to its limit."""
    root = tiny.make_root(tmp_path, policy=policy, compact=compact, model=tiny.MLP)
    assert not (REPO / "bench" / "models" / "mlp.py").exists()
    cell = harness.load_cell(root, f"tiny_{policy}")
    result = harness.run(cell, 2**31 + 11, 0.2, False, 0.0, require_chip=False)
    assert result["correct"], result["checks"]
    assert 0 <= result["checks"]["frozen_gap"]["value"] <= 1e-5
    assert result["info"]["lockstep_epochs"] >= 1


def test_calibration_pass_on_the_tiny_cell(tmp_path, capsys):
    """One pass of ``bench/calibrate.py`` over the tiny cell on the CPU:
    the program's reading is under the tiny limits, the control's (the
    family's model in bfloat16) and a planted fault's are not."""
    from types import SimpleNamespace

    from bench import calibrate

    cell = harness.load_cell(tiny.make_root(tmp_path), "tiny_vaoi")
    args = SimpleNamespace(program_seeds="3", control_seeds="4", faults="unchanged_training",
                           fault_seeds="5", m_seeds="6", trace_out="")
    assert calibrate.calibrate(cell, args) == 0
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines() if x.startswith("{")]
    by_kind = {x["kind"]: x for x in lines}
    assert set(by_kind) == {"program", "control", "fault:unchanged_training", "m_gap"}
    limits = cell.limits["limits"]
    assert compare.judge(by_kind["program"], limits)[0]
    assert not compare.judge(by_kind["control"], limits)[0]
    assert not compare.judge(by_kind["fault:unchanged_training"], limits)[0]
    assert by_kind["m_gap"]["max_gap"] < 1e-4


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
