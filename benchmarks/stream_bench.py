"""Streaming-scenario bench: F1 + VAoI dynamics for every data-stream
scenario × selection policy (``repro/data/stream.py``, DESIGN.md §10).

Each cell runs a short solo simulation on a micro CNN and records the final
macro-F1, the VAoI trajectory summary (mean age, mean feature distance), and
epoch throughput.  Results go to stdout CSV (the ``benchmarks/run.py``
harness protocol) AND to ``BENCH_stream.json`` at the repo root — a
machine-readable perf/correctness-trajectory file validated by
``tools/check_bench.py`` in CI.

  PYTHONPATH=src python benchmarks/stream_bench.py           # 4x5 grid, quick
  PYTHONPATH=src python benchmarks/stream_bench.py --full    # larger protocol
"""
from __future__ import annotations

import argparse
import json
import os
import time
from pathlib import Path

import jax
import numpy as np

from repro.compile_cache import enable_compile_cache

ROOT = Path(__file__).resolve().parent.parent
OUT = ROOT / "BENCH_stream.json"

_MICRO = dict(image_size=8, conv_channels=(2, 2, 2, 2, 2, 2), fc_dims=(8,))

# mean-matched streaming params sized to the quick protocol's T
_STREAM_PARAMS = {
    "static": (),
    "drift": (("period", 8.0), ("alpha", 0.3)),
    "arrival": (("rate", 4.0), ("burst", 2.0), ("window", 16.0)),
    "shift": (("period", 4.0), ("num_phases", 2.0)),
}


def _world(num_clients: int, samples: int):
    from repro.configs.cifar_cnn import CNNConfig
    from repro.data import make_federated_dataset
    from repro.fl import cnn_backend

    cnn = CNNConfig(name="stream-micro", **_MICRO)
    data = make_federated_dataset(
        jax.random.PRNGKey(0), num_clients=num_clients,
        samples_per_client=samples, alpha=0.3, test_size=64, image_size=8,
    )
    return data, cnn_backend(cnn)


def bench_one(
    scenario: str, policy: str, data, backend, epochs: int, n: int,
    compact: bool = False,
) -> dict:
    from repro.core import EHFLConfig, run_simulation

    cfg = EHFLConfig(
        num_clients=n, epochs=epochs, slots_per_epoch=8, kappa=4,
        p_bc=0.4, k=max(1, n // 4), mu=0.3, e_max=8, policy=policy,
        eval_every=epochs, probe_size=4, stream=scenario,
        stream_params=_STREAM_PARAMS[scenario],
        compact="auto" if compact else False,
    )
    t0 = time.time()
    out = jax.block_until_ready(run_simulation(cfg, backend, data))
    wall = time.time() - t0
    m = out["metrics"]
    return {
        "scenario": scenario,
        "policy": policy,
        "compact": compact,
        "epochs": epochs,
        "N": n,
        "f1": round(float(np.asarray(m["f1"])[-1]), 4),
        "avg_age_mean": round(float(np.asarray(m["avg_age"]).mean()), 4),
        "avg_m_mean": round(float(np.asarray(m["avg_m"]).mean()), 5),
        "n_uploaded": int(np.asarray(m["n_uploaded"]).sum()),
        "epoch_s": round(wall / epochs, 4),
        "clients_per_s": round(n * epochs / max(wall, 1e-9), 1),
    }


def _compacts(policy: str, n: int) -> tuple:
    """Row variants per cell: always dense; plus a compact row when the
    policy's slab is actually below N (fedavg auto-falls-back dense, so a
    second identical row would be noise)."""
    from repro.core import EHFLConfig
    from repro.core.policies import make_policy
    from repro.core.simulator import resolve_compact_cap

    cfg = EHFLConfig(num_clients=n, k=max(1, n // 4), policy=policy)
    spec = make_policy(policy, num_clients=n, k=cfg.k)
    return (False, True) if resolve_compact_cap(cfg, spec) else (False,)


def run(quick: bool = True) -> list:
    """benchmarks/run.py suite entry: the scenario × policy × {dense,
    compact} grid, written to BENCH_stream.json, returned as harness CSV
    rows."""
    from repro.core import STREAM_SCENARIOS
    from repro.core.policies import POLICIES

    n, samples, epochs = (16, 32, 8) if quick else (64, 64, 32)
    data, backend = _world(n, samples)
    rows = [
        bench_one(sc, pol, data, backend, epochs, n, compact=c)
        for sc in STREAM_SCENARIOS
        for pol in POLICIES
        for c in _compacts(pol, n)
    ]
    OUT.write_text(json.dumps({
        "bench": "stream",
        "devices": len(jax.devices()),
        "backend": jax.default_backend(),
        "cpus": os.cpu_count(),
        "quick": quick,
        "rows": rows,
    }, indent=2))
    return [
        {
            "name": f"stream/{r['scenario']}_{r['policy']}"
            + ("_compact" if r["compact"] else ""),
            "us_per_call": r["epoch_s"] * 1e6,
            "derived": f"f1={r['f1']};age={r['avg_age_mean']};m={r['avg_m_mean']}",
        }
        for r in rows
    ]


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--full", action="store_true", help="larger N/T protocol")
    args = ap.parse_args()
    enable_compile_cache()
    print(f"devices: {len(jax.devices())} ({jax.default_backend()})")
    print("name,us_per_call,derived")
    for r in run(quick=not args.full):
        print(f"{r['name']},{r['us_per_call']:.1f},{r['derived']}")
    print(f"wrote {OUT}")


if __name__ == "__main__":
    main()
