"""Spans and scopes of the GK solve path (``repro.runtime.spans``): host
spans in a profiler trace of a host-loop estimate, named scopes in the
compiled in-graph estimate's HLO."""
import re
from collections import Counter
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from conftest import make_lowrank
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
    assert count["repro.op.matvec"] == 2 * kprime + 1
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
