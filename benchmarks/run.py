"""Benchmark harness — one module per paper table/figure + infra rooflines.

Prints ``name,us_per_call,derived`` CSV.  Default is the quick protocol
(CPU-feasible, same structural constants as the paper); ``--full`` runs the
3x3 (alpha x p_bc) grid at larger N/T.

The ``fleet``, ``stream``, and ``channel`` suites additionally write
machine-readable ``BENCH_*.json`` perf-trajectory files at the repo root
(validated by ``tools/check_bench.py``).

Every suite runs under a wall-clock watchdog (``--suite-timeout``, default
900 s): a suite that hangs — a deadlocked collective, a runaway compile —
kills the harness with exit 1 instead of wedging CI until the job-level
timeout reaps it with no attribution.
"""
from __future__ import annotations

import argparse
import os
import sys
import threading
import time

from repro.compile_cache import enable_compile_cache


def _watchdog(suite: str, timeout_s: float) -> threading.Timer:
    """Arm a wall-clock kill switch for one suite.  ``os._exit`` (not
    ``sys.exit``) so a C-level hang inside XLA can't swallow the exit —
    a watchdog that raises in a side thread would be silently dropped."""

    def _kill() -> None:
        print(
            f"{suite}/TIMEOUT,0,exceeded {timeout_s:.0f}s wall clock",
            file=sys.stderr, flush=True,
        )
        os._exit(1)

    t = threading.Timer(timeout_s, _kill)
    t.daemon = True
    t.start()
    return t


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument(
        "--only",
        default=None,
        help="comma list from: fig4,fig5,fig6,roofline,kernels,ablation,fleet,stream,channel",
    )
    ap.add_argument(
        "--suite-timeout", type=float, default=900.0,
        help="per-suite wall-clock limit in seconds; a suite that exceeds it "
        "fails the harness (exit 1) instead of hanging",
    )
    args = ap.parse_args()
    enable_compile_cache()
    quick = not args.full

    from benchmarks import (
        ablation_mu, channel_bench, fig4_f1, fig5_vaoi, fig6_energy,
        fleet_bench, kernels_bench, roofline, stream_bench,
    )

    suites = {
        "kernels": kernels_bench.run,
        "roofline": roofline.run,
        "fig4": fig4_f1.run,
        "fig5": fig5_vaoi.run,
        "fig6": fig6_energy.run,
        "ablation": ablation_mu.run,
        "fleet": fleet_bench.run,
        "stream": stream_bench.run,
        "channel": channel_bench.run,
    }
    wanted = args.only.split(",") if args.only else list(suites)

    print("name,us_per_call,derived")
    failed = []
    for name in wanted:
        if name not in suites:
            print(f"{name}/ERROR,0,UnknownSuite", file=sys.stderr)
            failed.append(name)
            continue
        t0 = time.time()
        watchdog = _watchdog(name, args.suite_timeout)
        try:
            rows = suites[name](quick=quick)
        except Exception as e:  # keep the harness going, but record the failure
            print(f"{name}/ERROR,0,{type(e).__name__}:{e}")
            failed.append(name)
            continue
        finally:
            watchdog.cancel()
        for r in rows:
            print(f"{r['name']},{r['us_per_call']:.1f},{r['derived']}")
        print(f"{name}/_suite_wall,{(time.time()-t0)*1e6:.0f},ok", file=sys.stderr)
    if failed:
        # CI gates on this: a broken suite must fail the job, not exit 0
        print(f"FAILED suites: {','.join(failed)}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
