"""Readings behind the limits of ``bench/limits/<workload>.json``.

  python bench/calibrate.py --workload paper_vaoi --program-seeds 1,2,3 \\
      --control-seeds 4,5,6 --faults inverted_selection --fault-seeds 7,8,9 \\
      --m-seeds 7,8 [--trace-out bench/tests/data/x.xplane.pb]

Runs on the chip, in one process, at the cell's own sizes:

* ``program``: one call of the cell's entry point per seed, checked by
  ``bench/compare.py`` exactly as a benchmark run checks its last call;
* ``control``: the reference in bfloat16 at default precision
  (``reference.CONTROL``) put in the program's place, checked the same way;
* ``fault``: the program with one fault of ``bench/faults.py`` planted, one
  call per seed, checked the same way;
* ``m_gap``: the program stepped one epoch at a time beside the lockstep
  reference, with the largest per-client gap of the VAoI distance ``M_i``
  (what the lockstep's ``margin`` has to cover);
* ``--trace-out``: a small traced run of the program, kept as the test
  fixture of ``bench/trace.py``.

Prints one JSON line per reading.
"""
import argparse
import json
import shutil
import sys
import time
from pathlib import Path

ROOT = Path.cwd()


def _seeds(s: str):
    return [int(x) for x in s.split(",") if x]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--program-seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--faults", default="")
    ap.add_argument("--fault-seeds", default="")
    ap.add_argument("--m-seeds", default="")
    ap.add_argument("--trace-out", default="")
    args = ap.parse_args()
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

    import jax
    import numpy as np

    from bench import compare, faults, harness, reference
    from bench.data import make_dataset

    cell = harness.load_cell(ROOT, args.workload)
    harness.check_devices(cell.workload["chips"])
    harness.enable_cache(ROOT)
    sim, model, dcfg = harness.sim_settings(cell), cell.config["model"], cell.config["data"]
    N, H, policy = sim["num_clients"], cell.traffic["horizon"], cell.traffic["policy"]
    margin = cell.limits["margin"]
    be, call = harness.backend(cell), harness.entry(cell)

    def emit(kind, seed, **kw):
        print(json.dumps({"kind": kind, "seed": seed, **kw}), flush=True)

    def check(kind, seed, got, data, t0):
        ref = reference.simulate(sim, model, data, seed, H, policy,
                                 witness=compare.age_sums(got["metrics"]["avg_age"], N),
                                 margin=margin)
        f1 = reference.eval_f1(model, got["global_params"], data)
        nums = compare.numbers(got, ref, f1, N)
        emit(kind, seed, **nums, min_margin=ref["min_margin"], seconds=time.perf_counter() - t0)

    def program(kind, seeds):
        be = harness.backend(cell)  # after any fault's patch
        for seed in seeds:
            t0 = time.perf_counter()
            data = make_dataset(seed, dcfg, model)
            out = call(harness.sim_config(cell, seed, H), be, data)
            got = jax.tree.map(np.asarray, harness._keep(out))
            del out
            check(kind, seed, got, data, t0)

    program("program", _seeds(args.program_seeds))
    for name in [f for f in args.faults.split(",") if f]:
        patch = faults.Patch()
        faults.FAULTS[name](patch)
        program(f"fault:{name}", _seeds(args.fault_seeds))
        patch.undo()

    for seed in _seeds(args.control_seeds):
        t0 = time.perf_counter()
        data = make_dataset(seed, dcfg, model)
        c = reference.simulate(sim, model, data, seed, H, policy, numerics=reference.CONTROL)
        got = {
            "metrics": {**c["metrics"], "n_delivered": c["metrics"]["n_uploaded"], "f1": c["f1"]},
            "global_params": c["global_params"], "msg_params": c["msg_params"],
            "final": c["final"],
        }
        check("control", seed, got, data, t0)

    if args.m_seeds:
        from repro.core import simulator, vaoi

        for seed in _seeds(args.m_seeds):
            t0 = time.perf_counter()
            data = make_dataset(seed, dcfg, model)
            cfg = harness.sim_config(cell, seed, H)
            step = jax.jit(simulator.make_epoch_fn(cfg, be, data))
            probe = data["images"][:, : sim["probe_size"]]
            dist = jax.jit(lambda c: vaoi.feature_distance(
                jax.lax.map(lambda im: be.feature(c.global_params, im), probe, batch_size=10), c.h))
            carry, ms, sums = simulator.init_carry(cfg, be), [], []
            for t in range(H):
                ms.append(np.asarray(dist(carry)))
                carry, met = step(carry, np.int32(t))
                sums.append(int(np.rint(float(met["avg_age"]) * N)))
            log = []
            ref = reference.simulate(sim, model, data, seed, H, policy, witness=sums,
                                     margin=margin, m_log=log)
            gaps = [float(np.max(np.abs(ms[t] - log[t]))) for t in range(len(log))]
            emit("m_gap", seed, t_stop=ref["t_stop"], max_gap=max(gaps) if gaps else None,
                 per_epoch=gaps, min_margin=ref["min_margin"], seconds=time.perf_counter() - t0)

    if args.trace_out:
        record_trace(cell, be, call, Path(args.trace_out))
    return 0


def record_trace(cell, be, call, dest: Path) -> None:
    """Two short calls at 10 clients, traced with the benchmark's spans."""
    import dataclasses

    import jax

    from bench import harness
    from bench import trace as tr
    from bench.data import make_dataset

    small = dataclasses.replace(cell, config={
        **cell.config,
        "data": {**cell.config["data"], "num_clients": 10, "samples_per_client": 40,
                 "test_size": 50},
        "sim": {**cell.config["sim"], "num_clients": 10, "k": 2, "p_bc": 0.5},
    })
    data = make_dataset(0, small.config["data"], small.config["model"])
    cfg = harness.sim_config(small, 0, small.config["sim"]["eval_every"])
    jax.block_until_ready(call(cfg, be, data))
    tmp = ROOT / "bench" / ".trace" / "fixture"
    shutil.rmtree(tmp, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp), profiler_options=opts)
    with jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
        for _ in range(2):
            with jax.profiler.TraceAnnotation(tr.CALL_SPAN):
                jax.block_until_ready(call(cfg, be, data))
    jax.profiler.stop_trace()
    from jax.profiler import ProfileData

    src = tr.find_xplane(str(tmp))
    for plane in ProfileData.from_file(src).planes:
        print(json.dumps({"kind": "plane", "name": plane.name, "lines": [
            [ln.name, sum(1 for _ in ln.events), [e.name for e in list(ln.events)[:5]]]
            for ln in plane.lines]}), flush=True)
    dest.parent.mkdir(parents=True, exist_ok=True)
    shutil.copy(src, dest)
    shutil.rmtree(tmp, ignore_errors=True)
    t = tr.load(str(dest))
    print(json.dumps({"kind": "trace", "path": str(dest), "bytes": dest.stat().st_size,
                      "window_s": t.window_s, "busy_s": tr.busy_s(t),
                      "modules": sorted({n.split("(")[0] for evs in t.modules.values() for n, _, _ in evs}),
                      "top_ops": tr.top_ops(t), "idle_gaps": tr.idle_gaps(t)}), flush=True)


if __name__ == "__main__":
    sys.exit(main())
