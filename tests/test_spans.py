"""Spans and scopes of the GK solve path (``repro.runtime.spans``): host
spans in a profiler trace of a host-loop estimate, named scopes in the
compiled in-graph estimate's HLO."""
import re
from collections import Counter
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from conftest import make_lowrank, run_on_devices
from repro.api import SVDSpec, plan
from repro.core.linop import LinOp
from repro.core.rank import numerical_rank
from repro.runtime.spans import span


def op_names(compiled_text: str) -> set:
    return set(re.findall(r'op_name="([^"]*)"', compiled_text))


def host_spans(trace_dir: Path) -> list:
    """``[(name, start_ns, end_ns)]`` of the ``repro.*`` host spans in the
    newest trace under ``trace_dir``."""
    from jax.profiler import ProfileData
    files = sorted(trace_dir.rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    profile = ProfileData.from_file(str(files[-1]))
    return [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
            for plane in profile.planes if plane.name == "/host:CPU"
            for line in plane.lines for ev in line.events
            if ev.name.startswith("repro.")]


def op_scopes(compiled_text: str) -> tuple:
    """``(module name, {instruction: op_name})`` of a compiled program."""
    module = re.match(r"HloModule (\S+?),", compiled_text).group(1)
    return module, dict(re.findall(
        r'%(\S+) = [^\n]*metadata=\{op_name="([^"]*)"', compiled_text))


def device_op_runs(trace_dir: Path) -> Counter:
    """``{(hlo module, hlo op): runs}`` of the device ops in the newest
    trace under ``trace_dir`` (on the CPU, the XLA ops' events)."""
    from jax.profiler import ProfileData
    files = sorted(trace_dir.rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    profile = ProfileData.from_file(str(files[-1]))
    runs = Counter()
    for plane in profile.planes:
        for line in plane.lines:
            for ev in line.events:
                stats = dict(ev.stats)
                if "hlo_module" in stats and "hlo_op" in stats:
                    runs[stats["hlo_module"], stats["hlo_op"]] += 1
    return runs


def scope_runs(runs: Counter, programs: list, scope: str) -> int:
    """Runs of ``scope``: per program, the most runs of one op whose
    ``op_name`` holds ``scope``, summed over the programs."""
    total = 0
    for module, names in programs:
        total += max((n for (mod, op), n in runs.items()
                      if mod == module and scope in names.get(op, "")),
                     default=0)
    return total


def host_step_programs(A, k: int) -> list:
    """``op_scopes`` of the host loop's compiled steps at operand ``A``
    (f32, ``k`` iterations, two CGS passes), as the loop calls them."""
    from repro.core import gk
    from repro.core.operators import DenseOp
    first, left, right = gk._compiled_host_steps()
    (m, n), f32 = A.shape, jnp.dtype(jnp.float32)
    op = DenseOp(A)

    def v(*shape):
        return jax.ShapeDtypeStruct(shape, f32)
    lowered = [
        first.lower(op, v(m), k=k, dtype=f32, store=f32),
        left.lower(op, v(n), v(m), v(), v(m, k + 1), 1, v(), passes=2),
        right.lower(op, v(m), v(n), v(), v(n, k), 1, v(), passes=2)]
    return [op_scopes(x.compile().as_text()) for x in lowered]


def test_host_loop_estimate_records_one_sync_per_iteration(rng, tmp_path):
    A = make_lowrank(rng, 90, 60, 6)
    p = plan(SVDSpec(host_loop=True), like=A)
    key = jax.random.key(1)
    jax.block_until_ready(p.estimate(A, key=key).rank)     # compile eagerly
    with jax.profiler.trace(str(tmp_path)):
        est = p.estimate(A, key=key)
        jax.block_until_ready(est.rank)
    spans = host_spans(tmp_path)
    count = Counter(name for name, _, _ in spans)
    kprime = int(est.iterations)
    assert int(est.rank) == 6
    # α₁, then one read of (β, α) per GK iteration, the last one breaking
    assert count["repro.gk.sync"] == kprime + 1
    assert count["repro.plan.estimate"] == 1
    assert count["repro.rank.count"] == 1
    assert count["repro.gk.left"] == kprime
    assert count["repro.gk.right"] == kprime + 1
    # the sweeps of A run inside the compiled steps: their host spans open
    # only while a step is traced, and none is traced in the window; the
    # device ops carry the scopes instead, one sweep per half-step
    assert count["repro.op.matvec"] == 0
    runs = device_op_runs(tmp_path)
    programs = host_step_programs(A, min(A.shape))
    assert scope_runs(runs, programs,
                      "repro.gk.left/repro.op.matvec/") == kprime
    assert scope_runs(runs, programs,
                      "repro.gk.right/repro.op.matvec/") == kprime + 1
    assert scope_runs(runs, programs, "/repro.op.matvec/") == 2 * kprime + 1
    # every span of the call nests in the entry's span
    (_, lo, hi), = [s for s in spans if s[0] == "repro.plan.estimate"]
    assert all(lo <= s <= e <= hi for _, s, e in spans)


@pytest.mark.parametrize("closure", [False, True], ids=["DenseOp", "LinOp"])
def test_in_graph_estimate_carries_scopes(rng, closure):
    A = make_lowrank(rng, 80, 50, 5)
    operand = LinOp(A.shape, lambda p: A @ p, lambda q: A.T @ q,
                    A.dtype) if closure else A

    def estimate(key):
        return numerical_rank(operand, key=key, host_loop=False,
                              max_iters=12)

    names = op_names(jax.jit(estimate).lower(jax.random.key(0))
                     .compile().as_text())
    loop = "while/body/closed_call/"
    for scope in ("repro.gk.left/repro.op.matvec/",
                  "repro.gk.left/repro.op.cgs/",
                  "repro.gk.right/repro.op.matvec/",
                  "repro.gk.right/repro.op.cgs/"):
        assert any(loop + scope in n for n in names), scope
    assert any("/repro.rank.count/" in n for n in names)


def test_spans_nest():
    def f(x):
        with span("repro.outer"):
            y = x * 2.0
            with span("repro.inner"):
                return jnp.sin(y)

    names = op_names(jax.jit(f).lower(jnp.ones(4)).compile().as_text())
    assert any("repro.outer/repro.inner/sin" in n for n in names)
    assert any(n.endswith("repro.outer/mul") for n in names)


def all_reduce_scopes(compiled_text: str) -> list:
    """``op_name`` of each all-reduce in a compiled program."""
    return re.findall(r' all-reduce(?:-start)?\([^\n]*op_name="([^"]*)"',
                      compiled_text)


def test_sharded_all_reduces_carry_the_psum_scope():
    """On four virtual CPU devices: the all-reduce of each ``ShardedOp``
    half-step, and each one inside the in-graph loop of a sharded solve,
    carries ``repro.op.psum`` within its half-step's ``repro.gk.*``
    scope."""
    got = run_on_devices("""
        import sys
        sys.path.insert(0, "tests")
        from conftest import sharded_half_steps
        import repro.distributed.gk_dist as gk_dist
        from repro.api import SVDSpec
        from repro.distributed.matvec import sharded_operator
        mesh = jax.make_mesh((4,), ("data",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        steps = [exe.as_text()
                 for exe in sharded_half_steps(mesh, 96, 64, 20)]
        op = sharded_operator(jnp.zeros((96, 64)), mesh)
        spec = SVDSpec(method="fsvd_sharded", rank=5, max_iters=20)
        solve = jax.jit(
            lambda o, key: gk_dist.solve_fsvd_sharded(o, spec, key=key)
        ).lower(op, jax.random.key(0)).compile().as_text()
        print(json.dumps({"steps": steps, "solve": solve}))
    """, 4)
    for text in got["steps"]:
        names = all_reduce_scopes(text)
        assert len(names) == 1 and "/repro.op.psum/" in names[0], names
    loop = [n for n in all_reduce_scopes(got["solve"])
            if "/while/body/" in n]
    assert any("repro.gk.left/shard_map/repro.op.psum/" in n for n in loop)
    assert any("repro.gk.right/shard_map/repro.op.psum/" in n for n in loop)
    assert all("/repro.op.psum/" in n for n in loop), loop
