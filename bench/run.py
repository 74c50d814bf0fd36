"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, traffic mix and per-layer metrics are found
by name through ``BENCHMARK.json`` at the repository root.  Set-up
builds the cell's traces from the seed and loads and warms its one
program; then the window runs closed-loop sweeps of ``simulate_grid``
for ``--seconds``; then the results are compared with the plain
reference.  ``--trace 1`` records a short window with the profiler on
and reports the per-layer metrics instead of the end-to-end ones.

Without a TPU (or with fewer chips than the cell asks for) it exits
non-zero and prints no result.  The last line of standard output is one
JSON object.
"""
from __future__ import annotations

import argparse
import os
import sys
import time

T_IMPORT = time.time()
BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    import harness
    return harness.main(args.workload, args.seed, args.seconds,
                        bool(args.trace), t_start=harness.process_start(
                            T_IMPORT))


if __name__ == "__main__":
    sys.exit(main())
