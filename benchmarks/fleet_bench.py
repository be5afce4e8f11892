"""Fleet-scale epoch throughput: the client-sharded simulator
(``core/fleet.py``, DESIGN.md §9) swept over N on virtual host devices.

Standalone it virtualizes 8 CPU devices (the SNIPPETS.md
``--xla_force_host_platform_device_count`` idiom — the flag must be set
before jax initializes, which is why it happens at import, guarded on jax
not being loaded yet) and times one epoch of the jitted sharded program per
fleet size.  Under ``benchmarks/run.py`` it uses whatever devices exist.

Results go to stdout CSV (the harness protocol) AND to ``BENCH_fleet.json``
at the repo root — the machine-readable perf-trajectory file.  Every run
overwrites it with rows for the CURRENT topology (the ``devices``/``shards``
fields record which); the committed baseline is the standalone 8-device run.

  PYTHONPATH=src python benchmarks/fleet_bench.py            # N=1k..4k, 8 devices
  PYTHONPATH=src python benchmarks/fleet_bench.py --full     # N up to 64k
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

if __name__ == "__main__" and "jax" not in sys.modules:
    flags = os.environ.get("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()

import jax
import jax.numpy as jnp

from repro.compile_cache import enable_compile_cache

ROOT = Path(__file__).resolve().parent.parent
OUT = ROOT / "BENCH_fleet.json"

# micro CNN: 3 pools need 6 convs; image 8 -> 1x1 spatial, ~360 params, so
# msg_params stays ~100 MB even at N=64k
_MICRO = dict(image_size=8, conv_channels=(2, 2, 2, 2, 2, 2), fc_dims=(8,))


def _world(num_clients: int, samples: int = 8):
    from repro.configs.cifar_cnn import CNNConfig
    from repro.data import make_federated_dataset
    from repro.fl import cnn_backend

    cnn = CNNConfig(name="fleet-micro", **_MICRO)
    data = make_federated_dataset(
        jax.random.PRNGKey(0), num_clients=num_clients,
        samples_per_client=samples, alpha=0.5, test_size=64, image_size=8,
    )
    return data, cnn_backend(cnn)


def bench_one(
    num_clients: int, policy: str = "vaoi", reps: int = 3, compact: bool = False
) -> dict:
    """Time one jitted epoch of the sharded fleet program at this N.

    ``compact`` flips the active-set compaction of DESIGN.md §11: with the
    paper's k=10 budget only the 10 scheduled clients run the kappa-step
    SGD scan, so the dominant training FLOPs shrink ~N/k while the slot
    dynamics/probe pass stay N-wide — the dense-vs-compact row pairs
    measure exactly that lever."""
    from repro.core import EHFLConfig
    from repro.core.fleet import fleet_program

    cfg = EHFLConfig(
        num_clients=num_clients, epochs=1, slots_per_epoch=8, kappa=4,
        p_bc=0.3, k=10, mu=0.5, e_max=8,
        policy=policy, eval_every=1, probe_size=4,
        compact="auto" if compact else False,
    )
    data, backend = _world(num_clients)
    carry, scan_chunk, sharded, mesh = fleet_program(cfg, backend, data)
    ts = jnp.arange(1)
    args = (ts, sharded["images"], sharded["labels"])

    t0 = time.time()
    carry2, _ = jax.block_until_ready(scan_chunk(carry, *args))
    compile_s = time.time() - t0
    t0 = time.time()
    for _ in range(reps):
        carry2, _ = jax.block_until_ready(scan_chunk(carry2, *args))
    epoch_s = (time.time() - t0) / reps
    return {
        "N": num_clients,
        "shards": mesh.shape["data"],
        "policy": policy,
        "compact": compact,
        "k": cfg.k,
        "epoch_s": round(epoch_s, 4),
        "compile_s": round(compile_s, 2),
        "clients_per_s": round(num_clients / epoch_s, 1),
    }


def run(quick: bool = True) -> list:
    """benchmarks/run.py suite entry: sweep N x {dense, compact}, write
    BENCH_fleet.json, return the harness CSV rows."""
    ns = (1024, 4096) if quick else (1024, 4096, 16384, 65536)
    rows = [bench_one(n, compact=c) for n in ns for c in (False, True)]
    OUT.write_text(json.dumps({
        "bench": "fleet",
        "devices": len(jax.devices()),
        "backend": jax.default_backend(),
        # host fingerprint: tools/check_bench.py only gates throughput
        # against a baseline measured on a comparable machine
        "cpus": os.cpu_count(),
        "quick": quick,
        "rows": rows,
    }, indent=2))
    return [
        {
            "name": f"fleet/N{r['N']}_shards{r['shards']}"
            + ("_compact" if r["compact"] else ""),
            "us_per_call": r["epoch_s"] * 1e6,
            "derived": f"{r['clients_per_s']:.0f}clients/s",
        }
        for r in rows
    ]


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--full", action="store_true", help="sweep N up to 64k")
    args = ap.parse_args()
    enable_compile_cache()
    print(f"devices: {len(jax.devices())} ({jax.default_backend()})")
    print("name,us_per_call,derived")
    for r in run(quick=not args.full):
        print(f"{r['name']},{r['us_per_call']:.1f},{r['derived']}")
    print(f"wrote {OUT}")


if __name__ == "__main__":
    main()
