"""Drive a whole benchmark run on the CPU at a small size.

``drive`` skips only the harness's look for a chip: it loads the cell's
files, shrinks the operand to ``m × n``, and runs set-up, the window, the
sample and the comparison against the cell's committed limits, as a run
on the chip does.  ``FAULTS`` break the timed path underneath.
"""
from __future__ import annotations

import dataclasses
import importlib
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

SEED = 2**31 + 977


def unlisted_cell(config: str, traffic: str, limits_of: str):
    """A cell that ``BENCHMARK.json`` does not list, from its configuration
    and traffic files, held to the limits in ``bench/limits/<limits_of>``
    ``.json``; its metrics are those of the first listed cell."""
    import json
    from bench import harness

    def read(kind, name):
        return json.loads((ROOT / "bench" / kind / f"{name}.json").read_text())
    listed = json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]
    cfg = read("configs", config)
    return dataclasses.replace(
        harness.load_cell(listed[0]["name"]), name=f"{config}.{traffic}",
        chips=cfg["chips"], config=cfg, traffic=read("traffic", traffic),
        limits=read("limits", limits_of))


def drive(cell, m: int, n: int, seconds: float = 0.0,
          seed: int = SEED) -> dict:
    """``cell`` is a cell of ``BENCHMARK.json`` by name, or a ``Cell``."""
    from bench import harness
    if isinstance(cell, str):
        cell = harness.load_cell(cell)
    cell = dataclasses.replace(cell, config={**cell.config, "m": m, "n": n})
    return harness.run_cell(cell, seed, seconds, False, time.perf_counter(),
                            require_tpu=False)


def _alter_answer(mp):
    """An answer altered where it is produced: the registered solver's U
    has the sign of its largest entry flipped; a rank estimate's largest
    Ritz value is raised by a thousandth."""
    import jax.numpy as jnp
    from repro.api import registry
    from repro.core import rank
    get = registry.get_solver

    def get_solver(name):
        solver = get(name)

        def altered(*a, **kw):
            f = solver(*a, **kw)
            i = jnp.argmax(jnp.abs(f.U[:, 0]))
            return dataclasses.replace(f, U=f.U.at[i, 0].multiply(-1.0))
        return altered
    mp.setattr(importlib.import_module("repro.api.plan"), "get_solver",
               get_solver)
    estimate = rank.numerical_rank

    def altered_rank(*a, **kw):
        r = estimate(*a, **kw)
        return r._replace(eigenvalues=r.eigenvalues.at[0].multiply(1.001))
    mp.setattr(rank, "numerical_rank", altered_rank)


def _miscount(mp):
    """A rank estimate that counts one direction too many."""
    from repro.core import rank
    estimate = rank.numerical_rank

    def miscounted(*a, **kw):
        r = estimate(*a, **kw)
        return r._replace(rank=r.rank + 1)
    mp.setattr(rank, "numerical_rank", miscounted)


def _state_unchanged(mp):
    """A step that returns its state unchanged: the GK left half-step
    hands back its input vector."""
    import jax.numpy as jnp
    from repro.core.operators import DenseOp
    from repro.distributed.matvec import ShardedOp

    def step(self, p, y, alpha, basis, *, passes=2):
        return y, jnp.linalg.norm(y)
    mp.setattr(DenseOp, "lanczos_step", step)
    mp.setattr(ShardedOp, "lanczos_step", step)


def _no_exchange(mp):
    """The exchange between chips left out: every psum returns the local
    partial sum."""
    import jax
    mp.setattr(jax.lax, "psum", lambda x, axis_name, **kw: x)


FAULTS = {"alter_answer": _alter_answer, "state_unchanged": _state_unchanged,
          "no_exchange": _no_exchange, "miscount": _miscount}


class _Patch:
    """A minimal ``monkeypatch`` for child processes."""

    def __init__(self):
        self._undo = []

    def setattr(self, target, name, value):
        self._undo.append((target, name, getattr(target, name)))
        setattr(target, name, value)

    def undo(self):
        while self._undo:
            target, name, value = self._undo.pop()
            setattr(target, name, value)


if __name__ == "__main__":
    # python bench_drive.py <cell> <m> <n> <variant>...: one result line
    # per variant, "sound" or a name in FAULTS.  <cell> is a listed cell,
    # or <config>:<traffic>:<listed cell whose limits apply>.
    import json
    from repro.api.plan import clear_plan_cache
    cell, m, n = sys.argv[1], int(sys.argv[2]), int(sys.argv[3])
    if ":" in cell:
        cell = unlisted_cell(*cell.split(":"))
    for variant in sys.argv[4:]:
        patch = _Patch()
        if variant != "sound":
            FAULTS[variant](patch)
        clear_plan_cache()
        try:
            out = drive(cell, m, n)
        finally:
            patch.undo()
        print(json.dumps({"variant": variant, **out}), flush=True)
