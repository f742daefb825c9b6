"""The benchmark harness: one cell, one seed, one run.

A cell of ``BENCHMARK.json`` names a configuration and a traffic mix.
Everything that belongs to one of them sits in a file of its own, found
by name: ``bench/configs/<config>.json`` (the operand: shape, rank,
dtype, chips, layout), ``bench/traffic/<traffic>.json`` (the entry the
loop drives, ``solve`` or ``estimate``, and the ``SVDSpec`` it takes),
``bench/limits/<cell>.json`` (the limit
of each compared number) and ``bench/metrics/<metric>.py`` (a reader of
one per-layer metric).  A later change adds a cell by adding files.  A
metric named ``<quantity>.<part>`` is one quantity split over cells that
report different end-to-end metrics (``solve_s.host_loop``,
``gk_iters.host_loop``): it is measured as ``<quantity>`` is, by the
harness or by ``bench/metrics/<quantity>.py``.

A run:

1. finds the chips (a TPU, at least as many as the cell asks for, or it
   fails before printing a result);
2. set-up: makes A = M·N on the device in one jitted call from the seed,
   plans the solve, and runs it once, which compiles or hits the
   persistent compile cache in ``<checkout>/.jax_cache``;
3. the window: back-to-back calls of the entry in a closed loop, each
   with a fresh start key from the seed and each ending in
   ``block_until_ready`` of its answer (U, s and V of a solve; the rank
   and the Ritz values of a rank estimate), until ``seconds`` have
   passed; the window ends when the last call completes.  With ``trace``
   the profiler records the window;
4. reads the chips' peak memory, frees the program's state, and compares
   a sample of the window's answers, drawn from the seed, with the exact
   reference (``reference.py``);
5. prints each compared number with its limit, last on standard error and
   under ``checks`` in the result line, the last line of standard output.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Any, Optional

from bench import reference, roofline, tracing

ROOT = Path(__file__).resolve().parents[1]
BENCH = Path(__file__).resolve().parent
CHECKED = 8                  # answers of a window compared with the reference
ENTRIES = ("solve", "estimate")     # SolverPlan.solve, SolverPlan.estimate


class NoAccelerator(RuntimeError):
    """No TPU, or fewer chips than the cell asks for."""


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: tuple
    per_layer: tuple

    @property
    def entry(self) -> str:
        return self.traffic.get("entry", "solve")


def _reported(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """Resolve cell ``name`` of ``root/BENCHMARK.json`` to its files."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{sorted(cells)}")
    w = cells[name]
    cfg_entry = {c["name"]: c for c in spec["configs"]}[w["config"]]
    config = json.loads((root / cfg_entry["file"]).read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
    if "spec" not in traffic or not set(traffic) <= {"spec", "entry"}:
        raise ValueError(
            f"traffic {w['traffic']!r} has keys {sorted(traffic)}; the "
            "harness runs one closed loop of back-to-back calls and reads "
            "only 'spec' and 'entry'")
    if traffic.get("entry", "solve") not in ENTRIES:
        raise ValueError(f"traffic {w['traffic']!r}: entry must be one of "
                         f"{ENTRIES}, got {traffic['entry']!r}")
    limits = json.loads(
        (root / "bench" / "limits" / f"{name}.json").read_text())
    e2e = tuple(m for m in spec["end_to_end"] if _reported(m, name))
    moved = {m["name"] for m in e2e}
    layer = tuple(m for m in spec["per_layer"]
                  if _reported(m, name) and m["moves"] in moved)
    return Cell(name, int(w["chips"]), config, traffic, limits, e2e, layer)


def check_devices(chips: int, require_tpu: bool = True) -> list:
    """The first ``chips`` devices; :class:`NoAccelerator` unless they are
    TPUs (``require_tpu=False`` lets tests drive a run on the CPU)."""
    import jax
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise NoAccelerator(
            f"platform is {devices[0].platform!r}, not tpu; the benchmark "
            "measures the chip and never falls back to the CPU")
    if len(devices) < chips:
        raise NoAccelerator(
            f"the cell needs {chips} chips; JAX finds {len(devices)}")
    return devices[:chips]


def enable_compile_cache(root: Path = ROOT) -> str:
    """JAX's persistent compile cache: ``JAX_COMPILATION_CACHE_DIR`` when
    set (JAX reads it itself), else the fixed ``<checkout>/.jax_cache``.
    Every program is cached, however fast it compiled, so that only a
    checkout's first run compiles."""
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def seed_key(seed: int):
    """A PRNG key from all bits of ``seed`` (``jax.random.key`` alone keeps
    only the low 32)."""
    import jax
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


@dataclasses.dataclass
class Operand:
    M: Any
    N: Any
    A: Any
    op: Any             # what the solve takes: A, or a ShardedOp of it


def make_operand(config: dict, key, devices, backend: str) -> Operand:
    """A = M·N with Gaussian M (m, R), N (R, n), made on the device in one
    jitted call; ``layout: rows`` lays M and A out row-sharded over a
    ``("data",)`` mesh of ``devices`` and wraps A in a ``ShardedOp``."""
    import jax
    import jax.numpy as jnp
    m, n, rank = config["m"], config["n"], config["rank"]
    dtype = jnp.dtype(config["dtype"])

    def gen(key):
        km, kn = jax.random.split(key)
        M = jax.random.normal(km, (m, rank), jnp.float32)
        N = jax.random.normal(kn, (rank, n), jnp.float32)
        return M, N, jnp.dot(M, N, precision="highest").astype(dtype)

    if config["layout"] == "single":
        M, N, A = jax.jit(gen, out_shardings=jax.sharding.SingleDeviceSharding(
            devices[0]))(key)
        return Operand(M, N, A, A)
    if config["layout"] != "rows":
        raise ValueError(f"unknown layout {config['layout']!r}")
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.distributed.matvec import sharded_operator
    from repro.distributed.partition import operator_spec
    mesh = jax.make_mesh((len(devices),), ("data",), devices=devices,
                         axis_types=(jax.sharding.AxisType.Auto,))
    rows = NamedSharding(mesh, operator_spec(mesh))
    M, N, A = jax.jit(gen, out_shardings=(rows, NamedSharding(mesh, P()),
                                          rows))(key)
    return Operand(M, N, A, sharded_operator(A, mesh, backend=backend))


@dataclasses.dataclass
class Window:
    solves: int             # calls of the entry completed
    seconds: float
    iterations: list        # per call, device scalars until pulled
    kept: dict              # call index -> Factorization or RankEstimate


def dispatch(p, op, key, entry: str):
    """One call of the plan's ``entry``: its answer and its useful GK
    iterations, as device arrays that may still be in flight."""
    if entry == "estimate":
        est = p.estimate(op, key=key)
        return est, est.iterations
    fact, info = p.solve(op, key=key, with_info=True)
    return fact, info.iterations


def wait(result, entry: str) -> None:
    import jax
    if entry == "estimate":
        jax.block_until_ready((result.rank, result.eigenvalues))
    else:
        jax.block_until_ready((result.U, result.s, result.V))


def answer(result, entry: str) -> tuple:
    """What the comparison reads of one answer, on the host."""
    import numpy as np
    fields = (("rank", "eigenvalues") if entry == "estimate"
              else ("U", "s", "V"))
    return tuple(np.asarray(getattr(result, f)) for f in fields)


def solve_window(p, op, key, seconds: float, rng,
                 entry: str = "solve") -> Window:
    """Back-to-back calls for ``seconds``; ends when the last completes.
    Keeps a uniform sample of ``CHECKED`` answers (reservoir, ``rng``)."""
    import jax
    from jax.profiler import TraceAnnotation
    kept: dict = {}
    slots: list = []
    iterations = []
    n = 0
    t0 = time.perf_counter()
    with TraceAnnotation(tracing.WINDOW):
        while True:
            with TraceAnnotation("bench.dispatch"):
                fact, its = dispatch(p, op, jax.random.fold_in(key, n),
                                     entry)
            with TraceAnnotation("bench.wait"):
                wait(fact, entry)
            end = time.perf_counter()
            with TraceAnnotation("bench.record"):
                iterations.append(its)
                if n < CHECKED:
                    slots.append(n)
                    kept[n] = fact
                else:
                    j = int(rng.integers(0, n + 1))
                    if j < CHECKED:
                        del kept[slots[j]]
                        slots[j] = n
                        kept[n] = fact
            n += 1
            if end - t0 >= seconds:
                break
    return Window(n, end - t0, iterations, kept)


@dataclasses.dataclass
class Run:
    """What a per-layer metric reader gets (``bench/metrics/<name>.py``)."""
    cell: Cell
    spec: Any               # repro.api.SVDSpec
    plan: Any               # repro.api.SolverPlan
    op: Any
    devices: list
    peak: dict              # roofline.peaks(device_kind)
    solves: int
    window_s: float
    iterations: list        # ints, one per solve
    plan_traces: int        # solver traces during the window
    trace: Optional[tracing.TraceSummary]


def quantity(name: str) -> str:
    """What metric ``name`` measures: ``gk_iters.host_loop`` is
    ``gk_iters``, split by the end-to-end metric it moves."""
    return name.split(".")[0]


def load_reader(name: str):
    name = quantity(name)
    path = BENCH / "metrics" / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


def memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def check(kept: list, M, N, r: int, limits: dict,
          entry: str = "solve") -> tuple[dict, int]:
    """Worst compared numbers over the kept answers, and how many answers
    broke a limit.  A solve's answer is ``(U, s, V)``, compared over its
    top ``r`` triplets; an estimate's is ``(rank, eigenvalues)``."""
    ex = reference.exact(M, N)
    worst = {k: 0.0 for k in reference.NUMBERS[entry]}
    failed = 0
    for ans in kept:
        got = (reference.compare_rank(*ans, ex) if entry == "estimate"
               else reference.compare(*ans, ex, r))
        if any(got[k] > limits[k]["limit"] for k in got):
            failed += 1
        for k, v in got.items():
            worst[k] = max(worst[k], v)
    return worst, failed


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, t0: float,
             require_tpu: bool = True) -> dict:
    """One run of ``cell``; returns the result line as a dict.

    The program runs as a user calls it: the harness sets no precision of
    its own, so a product that the program leaves at the default runs at
    the default here too.  Only ``reference.py`` fixes its precisions."""
    import jax
    import numpy as np

    devices = check_devices(cell.chips, require_tpu)
    if not (ROOT / "src" / "repro").is_dir():
        raise FileNotFoundError(
            f"the program under test is missing: no {ROOT / 'src' / 'repro'}")
    if str(ROOT / "src") not in sys.path:
        sys.path.insert(0, str(ROOT / "src"))
    import repro.distributed.gk_dist  # noqa: F401  (registers fsvd_sharded)
    from repro.api import SVDSpec, plan
    from repro.api.plan import trace_count

    dev = devices[0]
    peak = roofline.peaks(dev.device_kind) if require_tpu else None
    spec = SVDSpec(**cell.traffic["spec"])
    stamps = [("start", t0), ("imports", time.perf_counter())]
    k_op, k_warm, k_win = jax.random.split(seed_key(seed), 3)
    operand = make_operand(cell.config, k_op, devices, spec.backend)
    jax.block_until_ready(operand.op)
    stamps.append(("operand", time.perf_counter()))
    p = plan(spec, like=operand.op)
    warm, _ = dispatch(p, operand.op, k_warm, cell.entry)
    wait(warm, cell.entry)
    del warm
    stamps.append(("warm_solve", time.perf_counter()))
    setup_s = stamps[-1][1] - t0
    print("setup " + " ".join(f"{b[0]}={b[1] - a[1]:.3f}s" for a, b in
                              zip(stamps, stamps[1:])), file=sys.stderr)

    traces0 = trace_count()
    rng = np.random.default_rng(seed & 0xFFFFFFFFFFFFFFFF)
    trace_dir = Path(tempfile.mkdtemp(prefix="bench-trace-")) if trace \
        else None
    try:
        if trace:
            with tracing.capture(trace_dir):
                win = solve_window(p, operand.op, k_win, seconds, rng,
                                   cell.entry)
            summary = tracing.reduce(tracing.load(trace_dir))
        else:
            win = solve_window(p, operand.op, k_win, seconds, rng,
                               cell.entry)
            summary = None
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)
    plan_traces = trace_count() - traces0
    mem = memory_peak(devices)
    iterations = [int(i) for i in jax.device_get(win.iterations)]

    metrics = {}
    run = None
    if trace:
        run = Run(cell, spec, p, operand.op, devices, peak, win.solves,
                  win.seconds, iterations, plan_traces, summary)
        for m in cell.per_layer:
            value = load_reader(m["name"])(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        e2e = {"solve_s": win.seconds / win.solves, "setup_s": setup_s}
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": e2e[quantity(m["name"])],
                                  "unit": m["unit"]}

    kept = [answer(f, cell.entry) for _, f in sorted(win.kept.items())]
    M, N = np.asarray(operand.M), np.asarray(operand.N)
    del operand, p, win, run
    worst, failed = check(kept, M, N, spec.rank, cell.limits, cell.entry)
    correct = failed == 0 and len(kept) > 0
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": mem}
    out = {"correct": correct, "attempted": len(iterations),
           "failed": failed, "metrics": metrics, "device": device,
           "gk_iterations": iterations}
    if trace:
        device["busy_s"] = summary.busy_mean_s([d.id for d in devices])
        device["window_s"] = summary.window_s
        out["breakdown"] = {"device_ops": tracing.top(summary.op_s),
                            "idle_gaps": tracing.top(summary.idle_by_phase)}
    out["checks"] = {k: {"value": worst[k], "limit": cell.limits[k]["limit"]}
                     for k in reference.NUMBERS[cell.entry]}
    return out


def main(workload: str, seed: int, seconds: float, trace: bool,
         t0: float) -> int:
    try:
        cell = load_cell(workload)
        enable_compile_cache()
        out = run_cell(cell, seed, seconds, trace, t0)
    except NoAccelerator as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    for k, c in out["checks"].items():
        print(f"check {k} = {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(f"check correct = {out['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0
