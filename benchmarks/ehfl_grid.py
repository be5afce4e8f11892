"""Shared EHFL sweep powering the Fig. 4 / 5 / 6 benchmarks.

Paper protocol (§V) scaled to this CPU container: the full protocol is
N=100 clients, T=500 epochs, 300 samples; the sweep below keeps every
structural constant (S=30, kappa=20, E_max=kappa+5, k=10 scaled to N,
mu=0.5, Dirichlet alpha grid, p_bc grid) and shrinks N/T/samples.

Every (policy, alpha, p_bc, scenario) cell runs its full multi-seed sweep
through ``repro.core.run_batch`` — the T-epoch simulation, eval included,
vmapped over the seed axis and executed as ONE jitted call (DESIGN.md §8) —
instead of one Python-loop ``run_simulation`` per seed.  Scalar fields of a
cell record ("f1", "avg_age", "energy_per_epoch", "total_energy") are means
across seeds; per-seed trajectories ride along under ``*_per_seed``.

Beyond the paper's homogeneous-Bernoulli energy model, the harvest-scenario
gallery (``repro.core.harvest``: bernoulli / markov / diurnal / hetero) runs
through the same engine via :func:`run_scenarios`.

Results are cached to experiments/ehfl_grid/<tag>.json.

CLI:
  PYTHONPATH=src python benchmarks/ehfl_grid.py --quick            # scenario gallery
  PYTHONPATH=src python benchmarks/ehfl_grid.py --quick --grid     # + full policy grid
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path
from typing import Sequence

import jax
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs.cifar_cnn import CNNConfig
from repro.core import SCENARIOS, EHFLConfig, run_batch
from repro.data import make_federated_dataset
from repro.fl import cnn_backend

CACHE = Path(__file__).resolve().parent.parent / "experiments" / "ehfl_grid"

BENCH_CNN = CNNConfig(name="bench", image_size=16, conv_channels=(8, 8, 16, 16, 32, 32), fc_dims=(64, 32))

POLICIES = ("vaoi", "fedavg", "fedbacys", "fedbacys_odd")

# the data partition and backend depend only on (N, samples, alpha, seed) /
# nothing — memoize so scenario/policy cells sharing them don't regenerate
_DATA_CACHE: dict = {}
_BACKEND = None


def _bench_backend():
    global _BACKEND
    if _BACKEND is None:
        _BACKEND = cnn_backend(BENCH_CNN)
    return _BACKEND


def _bench_data(num_clients: int, samples: int, alpha: float, seed: int):
    k = (num_clients, samples, alpha, seed)
    if k not in _DATA_CACHE:
        _DATA_CACHE[k] = make_federated_dataset(
            jax.random.PRNGKey(seed),
            num_clients=num_clients,
            samples_per_client=samples,
            alpha=alpha,
            test_size=300,
            image_size=BENCH_CNN.image_size,
        )
    return _DATA_CACHE[k]


def grid_settings(quick: bool):
    if quick:
        return dict(
            alphas=(0.1, 1.0),
            pbcs=(0.1, 1.0),
            num_clients=16,
            samples=40,
            epochs=30,
            eval_every=6,
            k=4,
            seeds=(0, 1),
        )
    return dict(
        alphas=(0.1, 1.0, 10.0),
        pbcs=(0.01, 0.1, 1.0),
        num_clients=40,
        samples=120,
        epochs=120,
        eval_every=10,
        k=8,
        seeds=(0, 1, 2),
    )


def run_cell(
    policy: str,
    alpha: float,
    p_bc: float,
    st: dict,
    seed: int = 0,
    scenario: str = "bernoulli",
    seeds: Sequence[int] | None = None,
) -> dict:
    """One sweep cell: all ``seeds`` in one batched, jitted call.

    ``seed`` is the base seed (data partition + default sweep offset);
    ``seeds`` defaults to ``st["seeds"]`` shifted by it.
    """
    if seeds is None:
        seeds = tuple(s + seed for s in st.get("seeds", (0,)))
    seeds = tuple(int(s) for s in seeds)
    tag = (  # d<seed> = data-partition seed; s<...> = sweep seeds
        f"{policy}_{scenario}_a{alpha}_p{p_bc}_N{st['num_clients']}_T{st['epochs']}"
        f"_n{st['samples']}_d{seed}_s{'-'.join(map(str, seeds))}"
    )
    CACHE.mkdir(parents=True, exist_ok=True)
    f = CACHE / f"{tag}.json"
    if f.exists():
        return json.loads(f.read_text())
    data = _bench_data(st["num_clients"], st["samples"], alpha, seed)
    cfg = EHFLConfig(
        num_clients=st["num_clients"],
        epochs=st["epochs"],
        slots_per_epoch=30,
        kappa=20,
        p_bc=p_bc,
        k=st["k"],
        mu=0.5,
        e_max=25,
        policy=policy,
        alpha=alpha,
        seed=seed,
        eval_every=st["eval_every"],
        probe_size=20,
        harvest=scenario,
    )
    t0 = time.time()
    out = run_batch(cfg, _bench_backend(), data, seeds)
    m = out["metrics"]  # every entry has a leading (len(seeds),) axis
    f1 = np.asarray(m["f1"], np.float64)
    rec = {
        "policy": policy,
        "alpha": alpha,
        "p_bc": p_bc,
        "scenario": scenario,
        "seeds": list(seeds),
        "wall_s": round(time.time() - t0, 1),
        "f1": f1.mean(0).tolist(),
        "f1_std": f1.std(0).tolist(),
        "f1_per_seed": f1.tolist(),
        "f1_epochs": np.asarray(m["f1_epochs"]).tolist(),
        "avg_age": np.asarray(m["avg_age"], np.float64).mean(0).tolist(),
        "energy_per_epoch": np.asarray(m["energy"], np.float64).mean(0).tolist(),
        "total_energy": float(np.asarray(m["total_energy"], np.float64).mean()),
        "total_energy_per_seed": np.asarray(m["total_energy"]).tolist(),
        "n_started": float(np.asarray(m["n_started"]).sum(-1).mean()),
        "n_uploaded": float(np.asarray(m["n_uploaded"]).sum(-1).mean()),
    }
    f.write_text(json.dumps(rec))
    return rec


def run_grid(quick: bool = True, seed: int = 0):
    st = grid_settings(quick)
    cells = {}
    for alpha in st["alphas"]:
        for p_bc in st["pbcs"]:
            for policy in POLICIES:
                cells[(policy, alpha, p_bc)] = run_cell(policy, alpha, p_bc, st, seed)
    return cells, st


def run_scenarios(quick: bool = True, seed: int = 0, policy: str = "vaoi"):
    """Harvest-scenario gallery at the paper's hardest cell (small alpha,
    scarce energy): every scenario, same mean rate, batched seed sweep."""
    st = grid_settings(quick)
    alpha = st["alphas"][0]
    p_bc = st["pbcs"][0] if quick else 0.1  # full grid's 0.01 is ultra-scarce
    cells = {}
    for scenario in SCENARIOS:
        cells[scenario] = run_cell(policy, alpha, p_bc, st, seed, scenario=scenario)
    return cells, st


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true", help="CPU-feasible settings")
    ap.add_argument("--grid", action="store_true", help="also run the policy grid")
    ap.add_argument("--policy", default="vaoi", choices=POLICIES)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()
    quick = args.quick

    cells, st = run_scenarios(quick, args.seed, args.policy)
    print(f"{'scenario':<11} {'final F1':>9} {'f1 std':>8} {'energy':>9} {'wall_s':>7}")
    for scenario, rec in cells.items():
        print(
            f"{scenario:<11} {rec['f1'][-1]:>9.4f} {rec['f1_std'][-1]:>8.4f} "
            f"{rec['total_energy']:>9.0f} {rec['wall_s']:>7.1f}"
        )
    if args.grid:
        grid, _ = run_grid(quick, args.seed)
        for (policy, alpha, p_bc), rec in grid.items():
            print(
                f"grid {policy:<13} a={alpha:<5} p={p_bc:<5} "
                f"f1={rec['f1'][-1]:.4f} energy={rec['total_energy']:.0f}"
            )


if __name__ == "__main__":
    main()
