"""One run of one benchmark cell: set-up, a measured window of simulator
calls, then the check against the plain reference.

Everything a cell needs is found by name (nothing here names a cell):

* ``BENCHMARK.json``              -- the cell: its configuration, traffic, chips;
* ``<config file>``               -- sizes of model, data and simulator;
* ``bench/models/<family>.py``    -- the model family the configuration names:
  the program's backend, the reference model, the data, operation counts;
* ``bench/traffic/<traffic>.json`` -- entry point, policy, horizon per call;
* ``bench/limits/<workload>.json`` -- the limits of the numbers compared;
* ``bench/metrics/<metric>.py``   -- one reader per per-layer metric.

A run: make the data (and any frozen weights) on the device from ``--seed``;
warm up with one call (it loads or compiles exactly the window's programs);
then call the entry point with seeds ``seed, seed+1, ...``, each call a fresh
``H``-epoch simulation, until ``--seconds`` have passed.  The last call, the
one that ends the window, is then checked against the reference.
"""
from __future__ import annotations

import dataclasses
import json
import os
import shutil
import sys
import time
from pathlib import Path
from types import ModuleType, SimpleNamespace
from typing import Any, Callable, Dict, List

import numpy as np

from bench import models

_COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)


class NoChip(RuntimeError):
    pass


# --------------------------------------------------------------------------
# Finding a cell's files by name
# --------------------------------------------------------------------------


@dataclasses.dataclass
class Cell:
    root: Path
    spec: dict  # BENCHMARK.json
    workload: dict
    config: dict
    traffic: dict
    limits: dict
    family: ModuleType  # bench/models/<config's model.family>.py

    @property
    def bench_dir(self) -> Path:
        return self.root / self.spec["paths"][0]

    def metrics(self, trace: bool) -> List[dict]:
        """The metrics this cell reports: end-to-end ones without a trace,
        per-layer ones with it; a metric with ``workloads`` only in those."""
        name = self.workload["name"]
        group = self.spec["per_layer" if trace else "end_to_end"]
        return [m for m in group if name in m.get("workloads", [name])]


def load_cell(root: Path, workload: str) -> Cell:
    spec = json.loads((root / "BENCHMARK.json").read_text())
    by_name = {w["name"]: w for w in spec["workloads"]}
    if workload not in by_name:
        raise KeyError(f"no workload {workload!r}; known: {sorted(by_name)}")
    w = by_name[workload]
    cfg = {c["name"]: c for c in spec["configs"]}[w["config"]]
    bench = root / spec["paths"][0]
    config = json.loads((root / cfg["file"]).read_text())
    return Cell(
        root=root,
        spec=spec,
        workload=w,
        config=config,
        traffic=json.loads((bench / "traffic" / f"{w['traffic']}.json").read_text()),
        limits=json.loads((bench / "limits" / f"{workload}.json").read_text()),
        family=models.load(bench, config["model"]),
    )


def load_reader(cell: Cell, metric: str) -> Callable:
    """``bench/metrics/<metric>.py``'s ``read(ctx)``."""
    path = cell.bench_dir / "metrics" / f"{metric}.py"
    return models.load_module(path, f"bench_metric_{metric}").read


# --------------------------------------------------------------------------
# The program under test
# --------------------------------------------------------------------------


# scenario keys a traffic file may set over its configuration's ``sim``
SCENARIO_KEYS = ("harvest", "harvest_params", "stream", "stream_params",
                 "channel", "channel_params")


def sim_settings(cell: Cell) -> dict:
    """The simulator settings of a cell: its configuration's ``sim`` block
    with the traffic's scenario keys over it.  Raises where they ask for a
    scenario the plain reference does not implement, so that no cell runs
    one and is checked as another."""
    from bench import reference

    s = {**reference.SUPPORTED, **cell.config["sim"],
         **{k: cell.traffic[k] for k in SCENARIO_KEYS if k in cell.traffic}}
    reference.check_supported(s)
    return s


def sim_config(cell: Cell, seed: int, epochs: int):
    from repro.core import EHFLConfig

    s, t = sim_settings(cell), cell.traffic
    params = lambda k: tuple(sorted(s.get(k, {}).items()))
    return EHFLConfig(
        num_clients=s["num_clients"], epochs=epochs, slots_per_epoch=s["slots_per_epoch"],
        kappa=s["kappa"], p_bc=s["p_bc"], k=s["k"], mu=s["mu"], lr=s["lr"],
        probe_size=s["probe_size"], e_max=s["e_max"], alpha=s["alpha"],
        eval_every=s["eval_every"], policy=t["policy"], compact=t["compact"], seed=seed,
        harvest=s["harvest"], harvest_params=params("harvest_params"),
        stream=s["stream"], stream_params=params("stream_params"),
        channel=s["channel"], channel_params=params("channel_params"),
    )


def make_frozen(cell: Cell, seed: int) -> Any:
    """The family's frozen weights from ``seed``, or ``None``."""
    make = getattr(cell.family, "make_frozen", None)
    return make(seed, cell.config["model"]) if make else None


def backend(cell: Cell, frozen: Any = None):
    return cell.family.program_backend(cell.config["model"], frozen)


def entry(cell: Cell) -> Callable:
    """``(cfg, backend, data) -> result`` of the traffic's entry point."""
    from repro import core

    name = cell.traffic["entry"]
    if name != "run_simulation":
        raise ValueError(f"unsupported entry {name!r}")
    use_kernel = bool(cell.traffic.get("use_kernel", False))
    return lambda cfg, be, data: core.run_simulation(cfg, be, data, use_kernel=use_kernel)


def _keep(out: Dict[str, Any], to_reference: Callable) -> Dict[str, Any]:
    """What the check reads of one call: its metrics, the global model and
    the final per-client state with every client's last message, the models
    as the reference's leaves (the family's ``to_reference``)."""
    c = out["carry"]
    return {"metrics": out["metrics"], "global_params": to_reference(out["global_params"]),
            "msg_params": to_reference(c.msg_params),
            "final": {"age": c.age, "battery": c.battery, "pending": c.pending}}


# --------------------------------------------------------------------------
# One run
# --------------------------------------------------------------------------


class Events:
    """JAX monitoring durations and counts, kept while ``on``."""

    def __init__(self):
        self.on = False
        self.durations: Dict[str, float] = {}
        self.counts: Dict[str, int] = {}

    def duration(self, event: str, secs: float, **_) -> None:
        if self.on:
            self.durations[event] = self.durations.get(event, 0.0) + secs

    def count(self, event: str, **_) -> None:
        if self.on:
            self.counts[event] = self.counts.get(event, 0) + 1


def check_devices(chips: int) -> list:
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU found (JAX platform {devices[0].platform!r})")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, found {len(devices)}")
    return devices


def enable_cache(root: Path) -> None:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout (or where ``JAX_COMPILATION_CACHE_DIR`` says), for every
    program however fast it compiles."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(root / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def run(cell: Cell, seed: int, seconds: float, trace: bool, t_start: float,
        require_chip: bool = True) -> dict:
    import jax

    from bench import compare, reference
    from bench import trace as tr

    chips = cell.workload["chips"]
    devices = check_devices(chips) if require_chip else jax.devices()
    enable_cache(cell.root)
    events = Events()
    jax.monitoring.register_event_duration_secs_listener(events.duration)
    jax.monitoring.register_event_listener(events.count)

    H = cell.traffic["horizon"]
    sim, model, fam = sim_settings(cell), cell.config["model"], cell.family
    frozen = make_frozen(cell, seed)
    be = backend(cell, frozen)
    call = entry(cell)
    data = fam.make_dataset(seed, cell.config["data"], model)
    jax.block_until_ready((frozen, data))
    data_s = time.perf_counter() - t_start

    # the warm-up is a whole call: besides the chunk and eval programs,
    # the drivers' host loop joins per-chunk metrics with eager ops whose
    # shapes follow the number of chunks
    warm = call(sim_config(cell, seed, H), be, data)
    jax.block_until_ready(warm)
    del warm
    setup_s = time.perf_counter() - t_start

    trace_dir = cell.bench_dir / ".trace" / cell.workload["name"]
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)

    # every call but the last drops its output as soon as it returns (the
    # N stacked messages would otherwise sit beside the next call's); the
    # last call, which ends the window, is the one checked
    started, call_s = [], []
    events.on = True
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
        while True:
            c0 = time.perf_counter()
            with jax.profiler.TraceAnnotation(tr.CALL_SPAN):
                out = call(sim_config(cell, seed + len(started), H), be, data)
                jax.block_until_ready(out)
            started.append(out["metrics"]["n_started"])
            call_s.append(time.perf_counter() - c0)
            if time.perf_counter() - t0 >= seconds:
                break
            del out
    window_s = time.perf_counter() - t0
    events.on = False
    if trace:
        jax.profiler.stop_trace()

    used = devices[:chips]
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in used)
    n_calls = len(started)
    n_started = int(sum(int(np.asarray(s).sum()) for s in started))
    pick = n_calls - 1
    checked = jax.tree.map(np.asarray, _keep(out, fam.to_reference))
    del out

    compile_s = sum(events.durations.get(e, 0.0) for e in _COMPILE_EVENTS)
    requests = events.counts.get("/jax/compilation_cache/compile_requests_use_cache", 0)
    hits = events.counts.get("/jax/compilation_cache/cache_hits", 0)
    window_line = {
        "setup_to_data_s": data_s, "setup_s": setup_s,
        "window_calls": n_calls, "call_s": call_s,
        "window_backend_compiles": requests - hits, "window_cache_loads": hits,
        "window_trace_lower_load_s": compile_s,
    }
    print(json.dumps(window_line), flush=True)

    # --- the check, once the window has closed and the peak is read ---
    N = sim["num_clients"]
    policy = cell.traffic["policy"]
    witness = compare.age_sums(checked["metrics"]["avg_age"], N)
    ref = reference.simulate(sim, fam, model, data, seed + pick, H, policy, frozen=frozen,
                             witness=witness, margin=cell.limits["margin"])
    f1_ref = reference.eval_f1(fam, model, checked["global_params"], data, frozen)
    nums = compare.numbers(checked, ref, f1_ref, N, fam, data)
    correct, rows = compare.judge(nums, cell.limits["limits"])
    print(json.dumps({"check_s": time.perf_counter() - t0 - window_s}), flush=True)

    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices),
              "memory_peak_bytes": int(peak)}
    epochs = n_calls * H
    ctx = SimpleNamespace(
        cell=cell, window_s=window_s, setup_s=setup_s, n_calls=n_calls, epochs=epochs,
        client_epochs=epochs * N, n_started=n_started, peak_bytes=peak, device=device,
        chips=chips, events=events, compile_s=compile_s, trace=None,
    )
    out_metrics, breakdown = {}, None
    if trace:
        ctx.trace = tr.load(tr.find_xplane(str(trace_dir)))
        shutil.rmtree(trace_dir, ignore_errors=True)
        busy = tr.busy_s(ctx.trace)
        device["busy_s"] = sum(busy.values()) / max(len(busy), 1)
        device["window_s"] = ctx.trace.window_s
        breakdown = {"device_ops": tr.top_ops(ctx.trace), "idle_gaps": tr.idle_gaps(ctx.trace)}
    for m in cell.metrics(trace):
        value = (_END_TO_END[m["name"]](ctx) if not trace
                 else load_reader(cell, m["name"])(ctx))
        if value is not None:
            out_metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    checks = {name: {"value": v, "limit": lim} for name, v, lim in rows}
    info = {k: v for k, v in nums.items() if k not in checks}
    result = {"correct": bool(correct), "attempted": n_calls, "failed": 0 if correct else 1,
              "metrics": out_metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["info"] = {**info, "horizon": H, "checked_call": pick}
    result["checks"] = checks
    return result


_END_TO_END = {
    "client_epochs_per_s": lambda ctx: ctx.client_epochs / ctx.window_s,
    "peak_hbm_gb": lambda ctx: ctx.peak_bytes / 1e9,
    "setup_s": lambda ctx: ctx.setup_s,
}


def print_result(result: dict) -> None:
    """The numbers compared, each beside its limit, as the last lines on
    standard error; the result as the last line on standard output."""
    print(f"info (not compared): {json.dumps(result['info'])}", file=sys.stderr)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)

