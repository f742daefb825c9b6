#!/usr/bin/env python3
"""Readings that a cell's limits are set from, in one process on the chip.

    python3 bench/calibrate.py --workload paper_dense_x1.rank \\
        --seeds 11,12,...,22 --control-seeds 31,32,33 --seconds 2

For each ``--seeds`` entry: a whole run of the cell (set-up, a short
window, the sampled comparison) and its compared numbers, the worst over
the run's checked answers.  For each ``--control-seeds`` entry: the
control, ``reference.control`` (the reference one precision step below
the configuration's; ``reference.control_rank`` for a rank estimate), put
in the program's place on that seed's operand
and judged by ``harness.check`` against the cell's committed limits, as a
run's answers are: its ``correct`` has to read false.  One JSON line per
reading; the benchmark's own runs never run the control.
"""
import argparse
import json
import sys
import time
from pathlib import Path


def _seeds(text: str) -> list:
    return [int(s) for s in text.split(",") if s]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=_seeds, default=[])
    ap.add_argument("--control-seeds", type=_seeds, default=[])
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
    from bench import harness, reference
    import jax
    import numpy as np

    cell = harness.load_cell(args.workload)
    harness.enable_compile_cache()
    for seed in args.seeds:
        out = harness.run_cell(cell, seed, args.seconds, False,
                               time.perf_counter())
        e2e = {harness.quantity(k): m["value"]
               for k, m in out["metrics"].items()}
        print(json.dumps({
            "reading": "program", "seed": seed, "correct": out["correct"],
            "solves": out["attempted"],
            "solve_s": e2e["solve_s"], "setup_s": e2e["setup_s"],
            "gk_iterations": out["gk_iterations"],
            **{k: c["value"] for k, c in out["checks"].items()}}),
            flush=True)
    devices = harness.check_devices(cell.chips)
    r = cell.traffic["spec"].get("rank")      # a solve's; an estimate's None
    for seed in args.control_seeds:
        k_op = jax.random.split(harness.seed_key(seed), 3)[0]
        operand = harness.make_operand(cell.config, k_op, devices, "xla")
        M, N = np.asarray(operand.M), np.asarray(operand.N)
        ex = reference.exact(M, N)
        if cell.entry == "estimate":
            answer = reference.control_rank(operand.A, ex.Qn)
        else:
            answer = tuple(np.asarray(x)
                           for x in reference.control(operand.A, ex.Qn, r))
        del operand
        worst, failed = harness.check([answer], M, N, r, cell.limits,
                                      cell.entry)
        print(json.dumps({"reading": "control", "seed": seed,
                          "correct": failed == 0, "failed": failed,
                          **worst}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
