"""End-to-end driver (deliverable b): the paper's §V experiment.

Trains the 6-conv CNN federatedly for a few hundred global rounds under
energy harvesting with VAoI scheduling, on the synthetic CIFAR-10-like
dataset (Dirichlet non-IID).  Defaults are CPU-feasible; pass --paper-scale
for the full N=100 / T=500 protocol on real hardware.

  PYTHONPATH=src python examples/ehfl_cifar.py --policy vaoi --rounds 200
"""
import argparse
import json
import time
from pathlib import Path

import jax
import numpy as np

from repro.checkpoint import save_pytree
from repro.compile_cache import enable_compile_cache
from repro.configs.cifar_cnn import CONFIG as PAPER_CNN
from repro.configs.cifar_cnn import CNNConfig
from repro.core import (
    CHANNEL_SCENARIOS,
    SCENARIOS,
    STREAM_SCENARIOS,
    EHFLConfig,
    run_batch,
    run_simulation,
)
from repro.data import make_federated_dataset
from repro.fl import cnn_backend


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--policy", default="vaoi",
                    choices=["vaoi", "fedavg", "fedbacys", "fedbacys_odd"])
    ap.add_argument("--rounds", type=int, default=None,
                    help="global epochs T (default 200; 500 under --paper-scale)")
    ap.add_argument("--clients", type=int, default=30)
    ap.add_argument("--samples", type=int, default=100)
    ap.add_argument("--alpha", type=float, default=0.1)
    ap.add_argument("--p-bc", type=float, default=0.1)
    ap.add_argument("--k", type=int, default=6)
    ap.add_argument("--mu", type=float, default=0.5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--harvest", default="bernoulli", choices=list(SCENARIOS),
                    help="energy-arrival scenario (repro.core.harvest)")
    ap.add_argument("--stream", default="static", choices=list(STREAM_SCENARIOS),
                    help="streaming-data scenario (repro.data.stream): static "
                         "is the paper's frozen partition; drift/arrival/shift "
                         "make client data non-stationary over epochs")
    ap.add_argument("--stream-period", type=float, default=0.0,
                    help="override the drift/shift period (epochs; 0 = scenario default)")
    ap.add_argument("--channel", default="ideal", choices=list(CHANNEL_SCENARIOS),
                    help="uplink channel scenario (repro.core.channel): ideal "
                         "is the paper's lossless uplink; erasure/aloha/fading "
                         "drop uploads, which retry with capped exponential "
                         "backoff and re-age their VAoI (DESIGN.md §12)")
    ap.add_argument("--channel-params", default="",
                    help="comma list of k=v channel knobs, e.g. "
                         "'p_loss=0.3,concentration=1.0' (erasure), "
                         "'num_channels=4' (aloha), 'p_bad=0.4,sojourn=2' (fading)")
    ap.add_argument("--num-seeds", type=int, default=1,
                    help=">1: vmapped multi-seed sweep in one jitted call (run_batch)")
    ap.add_argument("--fleet", action="store_true",
                    help="client-sharded fleet simulator (core/fleet.py) over all "
                         "visible devices; virtualize CPU devices with "
                         "XLA_FLAGS=--xla_force_host_platform_device_count=8")
    ap.add_argument("--paper-scale", action="store_true",
                    help="full paper protocol: N=100, T=500 (unless --rounds), "
                         "300 samples, 32px CNN")
    ap.add_argument("--out", default="experiments/ehfl_cifar")
    args = ap.parse_args()
    enable_compile_cache()
    if args.fleet and args.num_seeds > 1:
        ap.error("--fleet runs a single seed; drop --num-seeds "
                 "(seed sweeps go through run_batch, fleets through run_fleet)")

    if args.paper_scale:
        args.clients, args.samples, args.k = 100, 300, 10
        args.rounds = args.rounds or 500
        cnn, image = PAPER_CNN, 32
    else:
        args.rounds = args.rounds or 200
        cnn = CNNConfig(name="driver", image_size=16,
                        conv_channels=(16, 16, 32, 32, 64, 64), fc_dims=(128, 64))
        image = 16

    print(f"EHFL driver: policy={args.policy} N={args.clients} T={args.rounds} "
          f"alpha={args.alpha} p_bc={args.p_bc} harvest={args.harvest} "
          f"stream={args.stream} cnn={cnn.conv_channels}")
    data = make_federated_dataset(
        jax.random.PRNGKey(args.seed), num_clients=args.clients,
        samples_per_client=args.samples, alpha=args.alpha, test_size=500,
        image_size=image,
    )
    cfg = EHFLConfig(
        num_clients=args.clients, epochs=args.rounds, slots_per_epoch=30,
        kappa=20, p_bc=args.p_bc, k=args.k, mu=args.mu, e_max=25,
        policy=args.policy, alpha=args.alpha, seed=args.seed,
        eval_every=max(args.rounds // 10, 1), probe_size=20, lr=0.01,
        harvest=args.harvest, stream=args.stream,
        stream_params=(("period", args.stream_period),)
        if args.stream_period > 0 and args.stream in ("drift", "shift") else (),
        channel=args.channel,
        channel_params=tuple(
            (k, float(v))
            for k, v in (kv.split("=", 1) for kv in args.channel_params.split(",") if kv)
        ),
    )
    backend = cnn_backend(cnn)
    t0 = time.time()
    if args.fleet:
        from repro.core.fleet import run_fleet

        out = jax.block_until_ready(run_fleet(cfg, backend, data))
        wall = time.time() - t0
        m = out["metrics"]
        params = out["global_params"]
        print(f"fleet: N={args.clients} sharded over {out['num_shards']} device(s)")
    elif args.num_seeds > 1:
        seeds = [args.seed + i for i in range(args.num_seeds)]
        out = jax.block_until_ready(run_batch(cfg, backend, data, seeds))
        wall = time.time() - t0
        # report seed means (every metric has a leading seed axis except the
        # shared eval schedule); keep seed 0's model for the checkpoint
        m = {k: np.asarray(v) if k == "f1_epochs" else np.asarray(v).mean(0)
             for k, v in out["metrics"].items()}
        params = jax.tree.map(lambda x: x[0], out["global_params"])
    else:
        out = jax.block_until_ready(run_simulation(cfg, backend, data))
        wall = time.time() - t0
        m = out["metrics"]
        params = out["global_params"]
    outdir = Path(args.out)
    outdir.mkdir(parents=True, exist_ok=True)
    tag = f"{args.policy}_{args.harvest}_{args.stream}_a{args.alpha}_p{args.p_bc}"
    save_pytree(params, outdir / f"{tag}_model.npz")
    (outdir / f"{tag}_metrics.json").write_text(json.dumps({
        "f1": np.asarray(m["f1"]).tolist(),
        "f1_epochs": np.asarray(m["f1_epochs"]).tolist(),
        "avg_age": np.asarray(m["avg_age"]).tolist(),
        "energy": np.asarray(m["energy"]).tolist(),
        "total_energy": float(m["total_energy"]),
        "num_seeds": args.num_seeds,
        "wall_s": wall,
    }))
    print(f"f1 trajectory: {[round(float(x), 4) for x in np.asarray(m['f1'])]}")
    print(f"total energy: {float(m['total_energy']):.0f} units | "
          f"trainings: {int(np.asarray(m['n_started']).sum())} | wall: {wall:.1f}s")
    print(f"saved model+metrics -> {outdir}/{tag}_*")


if __name__ == "__main__":
    main()
