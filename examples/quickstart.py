"""Quickstart: VAoI-scheduled EHFL vs greedy FedAvg in ~a minute on CPU,
then the harvest-scenario gallery through the seed-vmapped sweep engine.

  PYTHONPATH=src python examples/quickstart.py
"""
import jax
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs.cifar_cnn import CNNConfig
from repro.core import SCENARIOS, EHFLConfig, run_batch, run_simulation
from repro.data import make_federated_dataset
from repro.fl import cnn_backend

enable_compile_cache()
cnn = CNNConfig(name="quick", image_size=16, conv_channels=(8, 8, 16, 16, 32, 32), fc_dims=(64, 32))
data = make_federated_dataset(
    jax.random.PRNGKey(0), num_clients=12, samples_per_client=60,
    alpha=0.1, test_size=200, image_size=16,
)
backend = cnn_backend(cnn)

print(f"{'policy':<14} {'final F1':>9} {'energy':>8} {'trainings':>10}")
for policy in ("vaoi", "fedavg", "fedbacys", "fedbacys_odd"):
    cfg = EHFLConfig(
        num_clients=12, epochs=25, slots_per_epoch=30, kappa=20, p_bc=0.3,
        k=4, mu=0.5, e_max=25, policy=policy, eval_every=25, probe_size=15, lr=0.05,
    )
    out = run_simulation(cfg, backend, data)
    m = out["metrics"]
    print(
        f"{policy:<14} {float(m['f1'][-1]):>9.4f} {float(m['total_energy']):>8.0f} "
        f"{int(m['n_started'].sum()):>10d}"
    )

# harvest-scenario gallery: same mean arrival rate, 2 seeds per scenario,
# each scenario's whole sweep is ONE jitted vmapped call (run_batch)
print(f"\n{'scenario':<11} {'final F1 (mean±std over seeds)':>31} {'energy':>8}")
for scenario in SCENARIOS:
    cfg = EHFLConfig(
        num_clients=12, epochs=10, slots_per_epoch=30, kappa=20, p_bc=0.3,
        k=4, mu=0.5, e_max=25, policy="vaoi", eval_every=10, probe_size=15,
        lr=0.05, harvest=scenario,
    )
    m = run_batch(cfg, backend, data, seeds=(0, 1))["metrics"]
    f1 = np.asarray(m["f1"])[:, -1]
    print(
        f"{scenario:<11} {f1.mean():>24.4f} ± {f1.std():.4f} "
        f"{float(np.asarray(m['total_energy']).mean()):>8.0f}"
    )
