"""A tiny cell for the CPU tests: a copy of the benchmark's files in a
temporary root, with one configuration, traffic mix, limits file and
workload added as new files and entries (no existing file is edited)."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]

MODEL = {"image_size": 8, "in_channels": 3, "num_classes": 10,
         "conv_channels": [4, 4, 8, 8, 8, 8], "fc_dims": [16, 8]}
DATA = {"num_clients": 8, "samples_per_client": 40, "alpha": 0.1, "test_size": 50, "noise": 0.8}
# mu sits inside the range of the tiny model's distances, so that ages grow
SIM = {"num_clients": 8, "slots_per_epoch": 10, "kappa": 4, "p_bc": 0.3, "k": 3,
       "mu": 0.12, "e_max": 6, "probe_size": 4, "lr": 0.05, "eval_every": 4, "alpha": 0.1}


def make_root(tmp: Path, policy: str = "vaoi", compact="auto", horizon: int = 8,
              limits_of: str | None = None, traffic_extra: dict | None = None) -> Path:
    """Copy ``BENCHMARK.json`` and ``bench/`` into ``tmp`` and add the tiny
    cell ``tiny_<policy>``; returns the new root.  The cell holds the tiny
    limits below, or ``limits_of``'s: a committed cell's limits file."""
    root = tmp / "root"
    shutil.copytree(REPO / "bench", root / "bench",
                    ignore=shutil.ignore_patterns(".jax_cache", ".trace", "__pycache__"))
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    bench = root / "bench"
    (bench / "configs" / "tiny.json").write_text(json.dumps(
        {"name": "tiny", "model": MODEL, "data": DATA, "sim": SIM}))
    spec["configs"].append({"name": "tiny", "source": "test", "file": "bench/configs/tiny.json",
                            "reduced": [], "why": "test"})
    traffic = {"entry": "run_simulation", "policy": policy, "compact": compact,
               "horizon": horizon, "modules": {"epoch": "^jit_chunk", "eval": "^jit__lambda"},
               **(traffic_extra or {})}
    (bench / "traffic" / f"tiny_{policy}.json").write_text(json.dumps(traffic))
    name = f"tiny_{policy}"
    limits = (json.loads((bench / "limits" / f"{limits_of}.json").read_text()) if limits_of
              else {"margin": 1e-4, "limits": {"int_mismatch": 0, "probe_m_gap": 1e-6,
                                               "age_level_gap": 1e-6, "param_change_gap": 1e-4,
                                               "f1_gap": 1e-6}})
    (bench / "limits" / f"{name}.json").write_text(json.dumps(limits))
    spec["workloads"].append({"name": name, "config": "tiny", "traffic": name, "chips": 1,
                              "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root
