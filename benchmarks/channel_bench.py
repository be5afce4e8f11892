"""Lossy-uplink channel bench: delivery/retry dynamics for every channel
scenario × selection policy (``repro/core/channel.py``, DESIGN.md §12).

Each cell runs a short solo simulation on the stream-bench micro world and
records the final macro-F1, the VAoI trajectory summary, the uplink outcome
counters (delivery rate, retries, drops), and epoch throughput.  Results go
to stdout CSV (the ``benchmarks/run.py`` harness protocol) AND to
``BENCH_channel.json`` at the repo root, validated by ``tools/check_bench.py``
in CI — including the contract that the ``ideal`` rows BIT-MATCH the
``BENCH_stream.json`` static cells (same world, same protocol constants:
the ideal channel is the pre-channel simulator).

The lossy axes sweep the knobs that matter per scenario: the erasure rows
sweep ``p_loss``, the ALOHA rows sweep ``num_channels`` (contention), the
fading row exercises the Gilbert–Elliott burst regime.

  PYTHONPATH=src python benchmarks/channel_bench.py           # quick grid
  PYTHONPATH=src python benchmarks/channel_bench.py --full    # larger protocol
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

try:  # harness mode (python -m benchmarks.run) vs script mode
    from benchmarks import stream_bench
except ImportError:  # script mode: benchmarks/ itself is sys.path[0]
    import stream_bench

ROOT = Path(__file__).resolve().parent.parent
OUT = ROOT / "BENCH_channel.json"


def bench_one(
    channel: str, params: tuple, policy: str, data, backend, epochs: int,
    n: int, compact: bool = False,
) -> dict:
    from repro.core import EHFLConfig, run_simulation

    # the stream-bench quick protocol constants, verbatim: ideal rows must
    # bit-match the BENCH_stream static cells (check_bench enforces this)
    cfg = EHFLConfig(
        num_clients=n, epochs=epochs, slots_per_epoch=8, kappa=4,
        p_bc=0.4, k=max(1, n // 4), mu=0.3, e_max=8, policy=policy,
        eval_every=epochs, probe_size=4,
        channel=channel, channel_params=params,
        compact="auto" if compact else False,
    )
    t0 = time.time()
    out = jax.block_until_ready(run_simulation(cfg, backend, data))
    wall = time.time() - t0
    m = out["metrics"]
    uploaded = int(np.asarray(m["n_uploaded"]).sum())
    delivered = int(np.asarray(m["n_delivered"]).sum())
    return {
        "scenario": channel,
        "params": dict(params),
        "policy": policy,
        "compact": compact,
        "epochs": epochs,
        "N": n,
        "f1": round(float(np.asarray(m["f1"])[-1]), 4),
        "avg_age_mean": round(float(np.asarray(m["avg_age"]).mean()), 4),
        "avg_m_mean": round(float(np.asarray(m["avg_m"]).mean()), 5),
        "n_uploaded": uploaded,
        "delivery_rate": round(delivered / max(uploaded, 1), 4),
        "retries": int(np.asarray(m["n_failed"]).sum()),
        "drops": int(np.asarray(m["n_dropped"]).sum()),
        "epoch_s": round(wall / epochs, 4),
        "clients_per_s": round(n * epochs / max(wall, 1e-9), 1),
    }


def _grid(n: int) -> list:
    """(channel, params, policy, compact) cells: ideal × every policy (the
    bit-match anchor rows, dense + compact like the stream bench), a
    loss-rate sweep on erasure, a contention sweep on ALOHA, and the bursty
    fading regime."""
    from repro.core.policies import POLICIES

    cells = [
        ("ideal", (), pol, c)
        for pol in POLICIES
        for c in stream_bench._compacts(pol, n)
    ]
    cells += [
        ("erasure", (("p_loss", p),), "vaoi", False) for p in (0.2, 0.5, 0.8)
    ]
    cells += [
        ("aloha", (("num_channels", float(M)),), "vaoi", False) for M in (1, 2, 4)
    ]
    cells += [
        ("fading", (("p_bad", 0.4), ("sojourn", 2.0)), "vaoi", False),
        ("erasure", (("p_loss", 0.3), ("concentration", 1.0)), "fedbacys", False),
    ]
    return cells


def run(quick: bool = True) -> list:
    """benchmarks/run.py suite entry: the channel grid, written to
    BENCH_channel.json, returned as harness CSV rows."""
    n, samples, epochs = (16, 32, 8) if quick else (64, 64, 32)
    data, backend = stream_bench._world(n, samples)
    rows = [
        bench_one(ch, params, pol, data, backend, epochs, n, compact=c)
        for ch, params, pol, c in _grid(n)
    ]
    OUT.write_text(json.dumps({
        "bench": "channel",
        "devices": len(jax.devices()),
        "backend": jax.default_backend(),
        "cpus": os.cpu_count(),
        "quick": quick,
        "rows": rows,
    }, indent=2))
    return [
        {
            "name": f"channel/{r['scenario']}_{r['policy']}"
            + "".join(f"_{k}{v:g}" for k, v in r["params"].items())
            + ("_compact" if r["compact"] else ""),
            "us_per_call": r["epoch_s"] * 1e6,
            "derived": f"f1={r['f1']};deliv={r['delivery_rate']}"
            f";retries={r['retries']};drops={r['drops']}",
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
