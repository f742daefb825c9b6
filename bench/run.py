#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload paper_dense_x1.fsvd --seed 7 \\
        --seconds 10 --trace 0

``--trace 0`` prints the cell's end-to-end metrics, ``--trace 1`` records
the window with the profiler and prints its per-layer metrics.  The last
line of standard output is the result as one JSON object; the compared
numbers and their limits are the last lines of standard error.  Without a
TPU, or with fewer chips than the cell asks for, the run exits 3 and
prints no result.  See ``bench/harness.py``.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
    from bench import harness
    return harness.main(args.workload, args.seed, args.seconds,
                        bool(args.trace), T0)


if __name__ == "__main__":
    sys.exit(main())
