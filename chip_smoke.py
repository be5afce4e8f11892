"""Run the paper's §V EHFL loop on a TPU through its normal entry points and
check what comes out.

  python chip_smoke.py             # one chip: the §V deployment, N=100
  python chip_smoke.py --chips 4   # four chips: the client-sharded fleet, N=400

The deployment is the one ``examples/ehfl_cifar.py --paper-scale`` builds:
``cifar_cnn.CONFIG`` (32 px, 6 conv + 3 FC, ~0.85 M parameters), 300
samples per client, Dirichlet alpha=0.1, S=30, kappa=20, k=10, p_bc=0.1.
Only the horizon is cut, to T=7 epochs.  At p_bc=0.1 a client harvests ~3
units per epoch, so the first kappa=20 trainings start at epoch 5 (0-based)
and two epochs train and aggregate.  The horizon also stops short of where
the compared runs part.  The TPU runs f32 convolutions at its default
(bf16-pass) precision, which turns a last-ulp difference in the FedAvg sum
into ~5e-4 in the params after one training epoch; VAoI's ``m >= mu``
threshold then turns that into different ages.  In T=12 runs on a v5e the
first age differs at epoch 8 for ``run_batch`` vs ``run_simulation`` and for
the fleet vs solo, and at epoch 10 for kernel vs jnp; the fleet's ``avg_m``
leaves its tolerance at epoch 7.

One chip (every phase in this one process):
  * ``run_simulation`` with ``vaoi`` (compacted training slab) and dense
    ``fedavg``;
  * both again with the Pallas kernels (``use_kernel=True``), compared with
    the jnp runs;
  * ``run_batch`` over 2 ``vaoi`` seeds, seed 0 compared with the solo run.
Four chips (``--chips 4``, this phase only):
  * ``run_fleet`` over a 4-device client mesh, compared with
    ``run_simulation`` on one of those chips (the sharded==solo contract of
    ``tests/test_fleet.py``).

A comparison holds integer dynamics (energy, starts, uploads, batteries,
pending flags) and VAoI ages exactly equal, and the float results within
the tolerances below.  Each phase prints one JSON line of smoke readings
(not benchmark metrics).  Any failed check raises, so the script exits
non-zero; the last line, ``{"ok": true, "device": {...}}``, is printed only
after every phase passed.  Without a TPU it exits 1 before any work.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

EPOCHS, EVAL_EVERY = 7, 4
# float contract of tests/test_fleet.py and tests/test_compact.py: summation
# order differs in the last ulp and kappa SGD steps per epoch amplify it;
# macro-F1 over the 500-image test set is argmax-discrete
PARAMS_ATOL = 1e-2
AVG_M_ATOL = 1e-3
F1_ATOL = 0.1
EXACT_METRICS = (
    "energy", "n_started", "n_uploaded", "n_delivered", "n_failed", "n_dropped",
    "avg_age", "f1_epochs",
)
EXACT_CARRY = ("age", "battery", "pending", "counter", "retries", "backoff")
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class SmokeFailure(AssertionError):
    pass


def _require(ok, what) -> None:
    """A check that ``python -O`` cannot strip: raise when ``ok`` is false."""
    if not ok:
        raise SmokeFailure(str(what))


def paper_setup(num_clients: int, epochs: int):
    """The §V deployment of ``examples/ehfl_cifar.py --paper-scale``, cut to
    ``epochs`` epochs."""
    import jax

    from repro.configs.cifar_cnn import CONFIG
    from repro.core import EHFLConfig
    from repro.data import make_federated_dataset
    from repro.fl import cnn_backend

    data = make_federated_dataset(
        jax.random.PRNGKey(0), num_clients=num_clients, samples_per_client=300,
        alpha=0.1, test_size=500, image_size=32,
    )
    cfg = EHFLConfig(
        num_clients=num_clients, epochs=epochs, slots_per_epoch=30, kappa=20,
        p_bc=0.1, k=10, mu=0.5, e_max=25, policy="vaoi", alpha=0.1, seed=0,
        eval_every=EVAL_EVERY, probe_size=20, lr=0.01,
    )
    return cfg, cnn_backend(CONFIG), data


_compile_s = [0.0]  # backend compile seconds so far, from JAX's own events


def _on_duration(event: str, duration: float, **_) -> None:
    if event == _COMPILE_EVENT:
        _compile_s[0] += duration


def run_phase(name: str, fn) -> dict:
    """Run ``fn`` (one driver call), wait for the device, print the phase
    line: compile seconds, and the rest of the host-clock wall time per
    epoch."""
    import jax
    import numpy as np

    c0, t0 = _compile_s[0], time.perf_counter()
    out = fn()
    jax.block_until_ready(out)
    wall = time.perf_counter() - t0
    compile_s = _compile_s[0] - c0
    _check_finite(name, out)
    m = out["metrics"]
    dev = jax.devices()[0]
    line = {
        "phase": name,
        "device_kind": dev.device_kind,
        "device_count": len(jax.devices()),
        "compile_s": compile_s,
        "steady_s_per_epoch": (wall - compile_s) / np.shape(m["n_started"])[-1],
        "f1_last": np.asarray(m["f1"])[..., -1].tolist(),
        "n_started_total": int(np.asarray(m["n_started"]).sum()),
        "peak_bytes_in_use": (dev.memory_stats() or {}).get("peak_bytes_in_use"),
    }
    print(json.dumps(line), flush=True)
    return out


def _check_finite(name: str, out: dict) -> None:
    import jax
    import numpy as np

    for where, tree in (("metrics", out["metrics"]), ("global_params", out["global_params"])):
        for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
            if not np.isfinite(np.asarray(leaf, np.float64)).all():
                raise SmokeFailure(f"{name}: non-finite {where}{jax.tree_util.keystr(path)}")


def _check_shapes(name: str, out: dict, cfg, lead: tuple = ()) -> None:
    import numpy as np

    m = out["metrics"]
    n_evals = -(-cfg.epochs // cfg.eval_every)
    _require(np.shape(m["n_started"]) == lead + (cfg.epochs,), (name, np.shape(m["n_started"])))
    _require(np.shape(m["f1"]) == lead + (n_evals,), (name, np.shape(m["f1"])))
    _require(np.shape(out["carry"].age) == lead + (cfg.num_clients,), name)
    f1 = np.asarray(m["f1"])
    _require(((f1 >= 0) & (f1 <= 1)).all(), (name, f1))
    _require((np.asarray(m["energy"]) >= 0).all(), name)


def compare(name: str, ref: dict, got: dict) -> None:
    """``got`` must match ``ref``: integer dynamics and ages exactly, float
    results within the stated tolerances.  Prints one line with every
    mismatch (where, and by how much), then raises if there was any."""
    import numpy as np

    mr, mg = ref["metrics"], got["metrics"]
    bad = []
    for k in EXACT_METRICS:
        a, b = np.asarray(mr[k]), np.asarray(mg[k])
        if not np.array_equal(a, b):
            bad.append({"metric": k, "first_epoch": int(np.argmax(a != b)),
                        "ref": a.tolist(), "got": b.tolist()})
    for f in EXACT_CARRY:
        a, b = np.asarray(getattr(ref["carry"], f)), np.asarray(getattr(got["carry"], f))
        if not np.array_equal(a, b):
            bad.append({"carry": f, "clients_differing": int((a != b).sum())})
    diffs = {
        "max_param_abs_diff": (_max_param_diff(ref, got), PARAMS_ATOL),
        "max_avg_m_abs_diff": (_max_abs_diff(mr["avg_m"], mg["avg_m"]), AVG_M_ATOL),
        "max_f1_abs_diff": (_max_abs_diff(mr["f1"], mg["f1"]), F1_ATOL),
    }
    bad += [{k: d, "atol": tol} for k, (d, tol) in diffs.items() if not d <= tol]
    print(json.dumps({"compare": name, "ok": not bad, **{k: d for k, (d, _) in diffs.items()},
                      "mismatches": bad}), flush=True)
    _require(not bad, f"{name}: {bad}")


def _max_abs_diff(a, b) -> float:
    import numpy as np

    return float(np.max(np.abs(np.asarray(a, np.float64) - np.asarray(b, np.float64))))


def _max_param_diff(ref: dict, got: dict) -> float:
    import jax

    return max(jax.tree.leaves(jax.tree.map(_max_abs_diff, ref["global_params"], got["global_params"])))


def one_chip(cfg, backend, data) -> None:
    """The §V deployment on one chip: vaoi compact and fedavg dense, each
    with and without the kernels, then the seed-vmapped sweep."""
    import dataclasses

    import jax
    import numpy as np

    from repro.core import run_batch, run_simulation
    from repro.core.simulator import resolve_compact_cap
    from repro.core.policies import make_policy

    fedavg = dataclasses.replace(cfg, policy="fedavg")
    for c, want_cap in ((cfg, cfg.k), (fedavg, None)):
        spec = make_policy(c.policy, num_clients=c.num_clients, k=c.k)
        _require(resolve_compact_cap(c, spec) == want_cap, (c.policy, want_cap))

    runs = {}
    for c, tag in ((cfg, "vaoi_compact"), (fedavg, "fedavg_dense")):
        ref = run_phase(tag, lambda: run_simulation(c, backend, data))
        _check_shapes(tag, ref, c)
        _require(int(np.asarray(ref["metrics"]["n_started"]).sum()) > 0, f"{tag}: nobody trained")
        if c.policy == "vaoi":
            _require((np.asarray(ref["metrics"]["n_started"]) <= c.k).all(), tag)
        runs[tag] = ref
    for c, tag in ((cfg, "vaoi_compact"), (fedavg, "fedavg_dense")):
        ker = run_phase(
            f"{tag}_kernel", lambda: run_simulation(c, backend, data, use_kernel=True),
        )
        compare(f"{tag} kernel vs jnp", runs[tag], ker)

    batch = run_phase("vaoi_run_batch_2_seeds", lambda: run_batch(cfg, backend, data, [0, 1]))
    _check_shapes("run_batch", batch, cfg, lead=(2,))
    seed0, seed1 = (
        {
            "metrics": {k: v if k == "f1_epochs" else v[i] for k, v in batch["metrics"].items()},
            "carry": jax.tree.map(lambda x: x[i], batch["carry"]),
            "global_params": jax.tree.map(lambda x: x[i], batch["global_params"]),
        }
        for i in (0, 1)
    )
    compare("run_batch seed 0 vs run_simulation", runs["vaoi_compact"], seed0)
    _require(_max_param_diff(seed0, seed1) > 0, "run_batch: seeds 0 and 1 identical")


def four_chips(cfg, backend, data) -> None:
    """``run_fleet`` over a 4-device client mesh vs ``run_simulation`` on one
    of those chips."""
    import jax

    from repro.core import run_fleet, run_simulation
    from repro.launch.mesh import make_fleet_mesh

    mesh = make_fleet_mesh(4, num_clients=cfg.num_clients)
    _require(mesh.shape["data"] == 4, f"fleet mesh shrank to {mesh.shape['data']} shards")
    fleet = run_phase("fleet_4_chips", lambda: run_fleet(cfg, backend, data, mesh=mesh))
    _check_shapes("fleet", fleet, cfg)
    _require(fleet["num_shards"] == 4, fleet["num_shards"])
    for leaf in jax.tree.leaves(fleet["carry"].msg_params):
        devices = {s.device for s in leaf.addressable_shards}
        _require(len(devices) == 4, f"msg_params leaf {leaf.shape} lives on {devices}")
    solo = run_phase("solo_1_chip", lambda: run_simulation(cfg, backend, data))
    compare("fleet vs solo", solo, fleet)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the client-sharded fleet phase, on four chips")
    args = ap.parse_args()

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform {devices[0].platform!r})", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} chips, found {len(devices)}",
              file=sys.stderr)
        return 1

    from repro.compile_cache import enable_compile_cache
    from repro.kernels import ops

    enable_compile_cache()
    _require(not ops._interpret(), "Pallas kernels would run in interpret mode on the TPU")
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    cache = Path(jax.config.jax_compilation_cache_dir)
    cache_entries = len(list(cache.iterdir())) if cache.is_dir() else 0

    num_clients = 400 if args.chips == 4 else 100
    t0 = time.perf_counter()
    setup = paper_setup(num_clients, EPOCHS)
    jax.block_until_ready(setup[2])
    print(json.dumps({
        "phase": "setup", "num_clients": num_clients, "seconds": time.perf_counter() - t0,
        "compile_cache": str(cache), "cache_entries_at_start": cache_entries,
    }), flush=True)
    (four_chips if args.chips == 4 else one_chip)(*setup)

    dev = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices()),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
