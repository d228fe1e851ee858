"""Benchmark entry: one run of one cell.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine that holds the chips the
cell asks for.  It refuses to run unless JAX's first device is a TPU,
builds the engine from the cell's configuration and traffic files, warms
every program the window uses, serves requests back to back for
``--seconds``, checks a sample of what was served against the plain
reference, and prints one JSON result line last on standard output.
``--trace 1`` runs the same window under the profiler and reports the
per-layer metrics instead of the end-to-end ones.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from benchlib import harness

    return harness.run(args.workload, args.seed, args.seconds,
                       bool(args.trace), T0)


if __name__ == "__main__":
    sys.exit(main())
