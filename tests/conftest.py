"""Shared fixtures.  NOTE: no XLA_FLAGS here on purpose — tests must see the
1 real CPU device; only the dry-run forces 512 placeholder devices (and the
distributed tests spawn subprocesses with their own flags).

Tests that need a real in-process mesh carry the ``distributed`` marker and
auto-skip below 8 devices; the dedicated CI job (and local runs of the
battery) provide them via

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
        PYTHONPATH=src python -m pytest -m distributed
"""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "distributed: needs >= 8 jax devices; run under "
        "XLA_FLAGS=--xla_force_host_platform_device_count=8 (auto-skipped "
        "otherwise)")


def pytest_collection_modifyitems(config, items):
    if jax.device_count() >= 8:
        return
    skip = pytest.mark.skip(
        reason="needs 8 devices; set "
               "XLA_FLAGS=--xla_force_host_platform_device_count=8")
    for item in items:
        if "distributed" in item.keywords:
            item.add_marker(skip)


@pytest.fixture(scope="session")
def mesh8():
    """Row-sharded 8-way mesh (the canonical distributed-test layout)."""
    from repro.launch.mesh import make_mesh
    return make_mesh((8,), ("data",))

try:                                  # hypothesis is a dev/CI requirement
    import os

    import hypothesis

    # CI runs the property suites under HYPOTHESIS_PROFILE=ci: fixed,
    # derandomized examples so per-PR runs are reproducible.  Hypothesis
    # does not read the env var on its own — load_profile is required.
    hypothesis.settings.register_profile(
        "ci", max_examples=40, deadline=None, derandomize=True)
    hypothesis.settings.register_profile(
        "dev", max_examples=10, deadline=None)
    hypothesis.settings.load_profile(
        os.environ.get("HYPOTHESIS_PROFILE", "dev"))
except ImportError:
    pass


@pytest.fixture(scope="module", autouse=True)
def _clear_jit_caches():
    """Drop compiled-executable caches between test modules.

    The suite compiles hundreds of distinct programs (kernel sweeps, ten
    architectures, trainer graphs); without this the CPU JIT's resident
    code pushes the host OOM near the end of a full run ("LLVM compilation
    error: Cannot allocate memory")."""
    yield
    jax.clear_caches()


@pytest.fixture(scope="session")
def rng():
    return jax.random.PRNGKey(0)


def make_lowrank(key, m: int, n: int, rank: int, dtype=jnp.float32):
    """Synthetic fixed-rank matrix, the paper's test input (§6.1)."""
    k1, k2 = jax.random.split(key)
    M = jax.random.normal(k1, (m, rank), dtype)
    N = jax.random.normal(k2, (rank, n), dtype)
    return M @ N


def run_on_devices(body: str, count: int) -> dict:
    """Run ``body`` in a fresh python that sees ``count`` virtual CPU
    devices (this process keeps its one); ``body`` must print a JSON dict
    as its last line, which is returned."""
    prog = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = \
            "--xla_force_host_platform_device_count={count}"
        import json
        import jax, jax.numpy as jnp
        import numpy as np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    """) + textwrap.dedent(body)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                         text=True, env=env, timeout=600, cwd=REPO)
    assert out.returncode == 0, f"stderr:\n{out.stderr[-4000:]}"
    return json.loads(out.stdout.strip().splitlines()[-1])


def sharded_half_steps(mesh, m: int, n: int, k: int) -> tuple:
    """``ShardedOp``'s compiled left and right half-steps for an (m, n) f32
    operand row-sharded over ``mesh``'s ``"data"`` axis, against the
    (m, k + 1) left and (n, k) right bases, as the in-graph loop calls
    them.  Only shapes go in, so ``mesh`` may be of described devices."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.distributed.matvec import ShardedOp

    def v(spec, *shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32,
                                    sharding=NamedSharding(mesh, spec))
    op = ShardedOp(v(P("data", None), m, n), mesh, lshape=(m, n))
    left = jax.jit(lambda o, p, y, Q: o.lanczos_step(p, y, 0.5, Q, passes=2))
    right = jax.jit(lambda o, q, y, B: o.lanczos_rstep(q, y, 0.5, B,
                                                       passes=2))
    return (left.lower(op, v(P(), n), v(P("data"), m),
                       v(P("data", None), m, k + 1)).compile(),
            right.lower(op, v(P("data"), m), v(P(), n),
                        v(P(), n, k)).compile())


def all_reduces(compiled_text: str) -> int:
    """All-reduce instructions in a compiled program's text."""
    return sum(line.count(" all-reduce(") + line.count(" all-reduce-start(")
               for line in compiled_text.splitlines())
