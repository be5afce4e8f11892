"""Run one benchmark cell once and print its result line.

  python bench/run.py --workload paper_vaoi --seed 7 --seconds 51 --trace 0

Run from the root of a checkout, on a machine with a TPU: it exits non-zero
and prints no result where JAX finds no TPU, or fewer chips than the cell
asks for.  ``--trace 1`` records a profiler trace of the window and reports
the cell's per-layer metrics instead of its end-to-end ones.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path.cwd()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness

    cell = harness.load_cell(ROOT, args.workload)
    try:
        result = harness.run(cell, args.seed, args.seconds, bool(args.trace), T_START)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    harness.print_result(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
