"""Compile the main-path Pallas kernels for a described TPU v5e chip.

Nothing runs: each case lowers a kernel with ``interpret=False`` against
shapes placed on one chip of a ``v5e:2x2`` topology description and asks
the TPU compiler for an executable, which refuses what the chip would
refuse (unaligned blocks, scalar stores to VMEM, more VMEM than the chip
has, unsupported gathers).  Shapes are those ``chip_smoke.py`` runs: the
(102400, 8192) dense operand against bases of 201 (fsvd, 200 iterations),
257 (the 256-iteration rank estimate) and 8193 columns (the rank
estimate's default ``max_iters = min(m, n)``), in f32 and bf16.  The
benchmark's operands follow: the host loop's step at one chip's shard,
and ``ShardedOp``'s half-steps over all four chips of the topology.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and under pytest-xdist every worker
imports this file.
"""
import contextlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh
from jax.sharding import SingleDeviceSharding

from conftest import all_reduces, run_on_devices, sharded_half_steps

from repro.kernels import (count_sketch, gk_matvec, gk_step, lowrank_update,
                           reorth, sketch_matvec, sparse_matvec)

M, N = 102400, 8192                  # chip_smoke's dense operand
WIDTHS = (201, 257, 8193)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:   # noqa: BLE001 — no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc


@contextlib.contextmanager
def _no_compile_cache():
    """The persistent compile cache off: an entry written for a described
    chip cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip."""
    with _no_compile_cache():
        yield SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def four_chips(topo):
    """A ``("data",)`` mesh over all four described chips."""
    with _no_compile_cache():
        yield Mesh(np.array(topo.devices), ("data",))


def _compile(sharding, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


F32, BF16, I32 = jnp.float32, jnp.bfloat16, jnp.int32


@pytest.mark.parametrize("dtype", [F32, BF16], ids=["f32", "bf16"])
@pytest.mark.parametrize("k", WIDTHS)
@pytest.mark.parametrize("kernel", ["mv_qtv", "rmv_qtv", "proj_qtv",
                                    "proj_norm"])
def test_gk_step_compiles_for_v5e(one_chip, kernel, k, dtype):
    """Each stage at the tiles ``ops.py`` picks for a k-column basis (the
    proj stages as both half-steps run them: left over m rows at ``bm``,
    right over n rows at ``bn``)."""
    bm, bn = gk_step.tiles(k)
    if kernel == "mv_qtv":
        fn = lambda A, p, y, Q: gk_step.mv_qtv(           # noqa: E731
            A, p, y, 0.5, Q, bm=bm, bn=bn, interpret=False)
        shapes = [((M, N), F32), ((N, 1), F32), ((M, 1), F32),
                  ((M, k), dtype)]
    elif kernel == "rmv_qtv":
        fn = lambda A, q, y, P: gk_step.rmv_qtv(          # noqa: E731
            A, q, y, 0.5, P, bm=bm, bn=bn, interpret=False)
        shapes = [((M, N), F32), ((M, 1), F32), ((N, 1), F32),
                  ((N, k), dtype)]
    else:
        rows, blk = (M, bm) if kernel == "proj_qtv" else (N, bn)
        stage = getattr(gk_step, kernel)
        fn = lambda u, Q, c: stage(u, Q, c, bm=blk,        # noqa: E731
                                   interpret=False)
        shapes = [((rows, 1), F32), ((rows, k), dtype), ((k, 1), F32)]
    exe = _compile(one_chip, fn, *shapes)
    assert "tpu_custom_call" in exe.as_text()


@pytest.mark.parametrize("right", [False, True], ids=["tapply", "rapply"])
def test_sketch_matvec_compiles_for_v5e(one_chip, right):
    """``Ψᵀ A`` (l = 256) and ``A Ω`` (k = 128) at the dense operand."""
    d = 128 if right else 256
    exe = _compile(one_chip, lambda s, i, X: sketch_matvec.sketch_matmat(
        s, i, X, bd=min(d, sketch_matvec.BD), right=right, interpret=False),
        ((d, sketch_matvec.ZETA), F32), ((d, sketch_matvec.ZETA), I32),
        ((M, N), F32))
    assert "tpu_custom_call" in exe.as_text()


def test_sparse_matvec_compiles_for_v5e(one_chip):
    """A 16384² ELL pack with 32 slots per row."""
    exe = _compile(one_chip, lambda v, c, x: sparse_matvec.sparse_matvec(
        v, c, x, interpret=False),
        ((16384, 32), F32), ((16384, 32), I32), ((1, 16384), F32))
    assert "tpu_custom_call" in exe.as_text()


@pytest.mark.parametrize("kernel", ["matvec_fused", "rmatvec_fused", "qtv",
                                    "subtract_qc", "lowrank_core"])
def test_other_solver_kernels_compile_for_v5e(one_chip, kernel):
    """The unfused GK matvecs at the dense operand, CGS against a 201-column
    basis, and the update path's (r+k, r+k) core (rank 8 + a rank-2 delta,
    full-extent blocks)."""
    k = WIDTHS[0]
    if kernel == "matvec_fused":
        fn = lambda A, p, y: gk_matvec.matvec_fused(        # noqa: E731
            A, p, y, 0.5, interpret=False)
        shapes = [((M, N), F32), ((N, 1), F32), ((M, 1), F32)]
    elif kernel == "rmatvec_fused":
        fn = lambda A, q, y: gk_matvec.rmatvec_fused(       # noqa: E731
            A, q, y, 0.5, interpret=False)
        shapes = [((M, N), F32), ((M, 1), F32), ((N, 1), F32)]
    elif kernel == "qtv":
        fn = lambda Q, v: reorth.qtv(Q, v, interpret=False)  # noqa: E731
        shapes = [((M, k), F32), ((M, 1), F32)]
    elif kernel == "subtract_qc":
        fn = lambda v, Q, c: reorth.subtract_qc(             # noqa: E731
            v, Q, c, interpret=False)
        shapes = [((M, 1), F32), ((M, k), F32), ((k, 1), F32)]
    else:
        fn = lambda C, o, D: lowrank_update.lowrank_matmul(  # noqa: E731
            C, o, D, bm=10, bn=10, interpret=False)
        shapes = [((10, 2), F32), ((2,), F32), ((2, 10), F32)]
    exe = _compile(one_chip, fn, *shapes)
    assert "tpu_custom_call" in exe.as_text()


def test_count_sketch_compiles_for_v5e(one_chip):
    """The serve path's entry fold: 64 entries × ζ slots into a panel
    padded to (128, 128)."""
    E = 64 * sketch_matvec.ZETA
    exe = _compile(one_chip, lambda r, c, v: count_sketch.scatter_add(
        r, c, v, (128, 128), interpret=False),
        ((E,), I32), ((E,), I32), ((E,), F32))
    assert "tpu_custom_call" in exe.as_text()


# The host loop's right half-step at the rank cells' operand (one chip's
# 24576-row shard of the paper's 1e5 x 8e4 operand, 200 iterations).
CELL_M, CELL_N, CELL_K = 24576, 80000, 200


def _host_right_step(sharding, m, n, k):
    """The compiled right half-step of ``gk_bidiag_host`` for an (m, n)
    f32 ``DenseOp`` and an (n, k) basis, as the loop calls it."""
    from repro.core import gk
    from repro.core.operators import DenseOp
    _, _, right = gk._compiled_host_steps()

    def v(*shape):
        return jax.ShapeDtypeStruct(shape, F32, sharding=sharding)
    return right.lower(DenseOp(v(m, n)), v(m), v(n), v(), v(n, k), 1, v(),
                       passes=2).compile()


def _entry_shapes(text: str) -> set:
    """Shapes of the buffers the ENTRY computation defines (shapes inside
    fused computations are not buffers)."""
    entry = text[text.index("\nENTRY"):]
    return set(re.findall(r"= (\w+\[[\d,]*\])", entry))


def _assert_reads_A_in_place(exe, m, n, k):
    stats = exe.memory_analysis()
    # eagerly, Aᵀ q made Aᵀ as an array: m·n·4 bytes of temporaries
    assert stats.temp_size_in_bytes < min(1 << 30, m * n * 4 // 8), stats
    # the donated basis is written in place
    assert stats.alias_size_in_bytes == n * k * 4, stats
    assert f"f32[{n},{m}]" not in _entry_shapes(exe.as_text())


def test_host_right_half_step_reads_A_in_place_on_v5e(one_chip):
    exe = _host_right_step(one_chip, CELL_M, CELL_N, CELL_K)
    _assert_reads_A_in_place(exe, CELL_M, CELL_N, CELL_K)
    assert f"f32[{CELL_N},{CELL_M}]" not in exe.as_text()


def test_host_right_half_step_reads_A_in_place_on_cpu():
    """The CPU twin at a small shape, for where no TPU compiler exists."""
    exe = _host_right_step(None, 96, 200, 20)
    _assert_reads_A_in_place(exe, 96, 200, 20)


# The four-chip cell's operand: the paper's 1e5 x 8e4 f32, row-sharded
# over ("data",), 25000 rows a chip, 200 GK iterations.
FULL_M, FULL_N, FULL_K = 100000, 80000, 200


def test_sharded_half_steps_on_v5e_2x2(four_chips):
    """One all-reduce a half-step, no temporaries to speak of, and no
    transposed copy of the 8 GB shard, which would double a chip's use
    to 16 GB."""
    shard = FULL_M // 4
    for exe in sharded_half_steps(four_chips, FULL_M, FULL_N, FULL_K):
        text = exe.as_text()
        assert all_reduces(text) == 1
        assert exe.memory_analysis().temp_size_in_bytes < 1 << 30
        assert f"f32[{FULL_N},{shard}]" not in text


def test_sharded_half_steps_on_cpu():
    """The CPU twin on four virtual devices at a small shape."""
    got = run_on_devices("""
        import sys
        sys.path.insert(0, "tests")
        from conftest import all_reduces, sharded_half_steps
        mesh = jax.make_mesh((4,), ("data",))
        print(json.dumps({"all_reduces": [
            all_reduces(exe.as_text())
            for exe in sharded_half_steps(mesh, 96, 64, 20)]}))
    """, 4)
    assert got["all_reduces"] == [1, 1]
