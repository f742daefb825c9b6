"""Algorithm 2 (F-SVD) against the dense SVD oracle + the paper's Table-2
error metrics and Figure-1 triplet-quality diagnostic."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import make_lowrank, run_on_devices
from repro.core.fsvd import fsvd, truncated_svd_errors
from repro.core.operators import DenseOp, LowRankOp
from repro.core.rsvd import rsvd


@pytest.mark.parametrize("host", [False, True])
@pytest.mark.parametrize("m,n,rank,r", [(200, 150, 30, 10), (120, 160, 20, 20)])
def test_fsvd_matches_dense_svd(rng, host, m, n, rank, r):
    A = make_lowrank(rng, m, n, rank)
    out = fsvd(A, r, 4 * rank, host_loop=host)
    U, s, Vt = jnp.linalg.svd(A, full_matrices=False)
    scale = float(s[0])
    np.testing.assert_allclose(np.asarray(out.s), np.asarray(s[:r]),
                               rtol=1e-4, atol=1e-5 * scale)
    # triplet quality (paper Fig 1): |u_i . u_i_svd| * |v_i . v_i_svd| ~ 1
    qual = np.abs(np.sum(np.asarray(out.U) * np.asarray(U[:, :r]), 0)) \
        * np.abs(np.sum(np.asarray(out.V) * np.asarray(Vt[:r].T), 0))
    np.testing.assert_allclose(qual, np.ones(r), atol=5e-3)


def test_table2_error_metrics(rng):
    """Relative error ||A^T U − V Σ||_F/||Σ||_F at machine-precision level
    (paper Table 2 reports ~1e-16/1e-17 in float64; f32 scale here)."""
    A = make_lowrank(rng, 300, 200, 40)
    out = fsvd(A, 20, 160, host_loop=True)
    errs = truncated_svd_errors(A, out)
    assert float(errs["relative"]) < 5e-6
    # rank-r residual == optimal Eckart-Young residual for r >= rank: here
    # r < rank so compare against the dense-SVD truncation residual.
    s = jnp.linalg.svd(A, compute_uv=False)
    opt = float(jnp.sqrt(jnp.sum(s[20:] ** 2)))
    assert float(errs["residual"]) < opt * 1.01 + 1e-3


def test_fsvd_full_rank_recovery(rng):
    """r == rank(A): reconstruction is exact (residual ~ 0)."""
    A = make_lowrank(rng, 150, 100, 12)
    out = fsvd(A, 12, 60, host_loop=True)
    errs = truncated_svd_errors(A, out)
    assert float(errs["residual"]) < 1e-2 * float(jnp.linalg.norm(A))


def test_fsvd_on_implicit_operator(rng):
    """The RSL path: operator given only by factors (never densified)."""
    k1, k2, k3 = jax.random.split(rng, 3)
    U = jnp.linalg.qr(jax.random.normal(k1, (120, 6)))[0]
    Vt = jnp.linalg.qr(jax.random.normal(k2, (80, 6)))[0].T
    s = jnp.sort(jax.random.uniform(k3, (6,)) + 0.5)[::-1]
    op = LowRankOp(U, s, Vt)
    out = fsvd(op, 6, 30)
    np.testing.assert_allclose(np.asarray(out.s), np.asarray(s), rtol=1e-4)


def test_fsvd_with_pallas_kernels(rng):
    A = make_lowrank(rng, 256, 192, 15)
    out_k = fsvd(DenseOp(A, backend="pallas"), 8, 60, host_loop=True)
    out_p = fsvd(DenseOp(A, backend="xla"), 8, 60, host_loop=True)
    np.testing.assert_allclose(np.asarray(out_k.s), np.asarray(out_p.s),
                               rtol=1e-4)


def test_fsvd_beats_default_rsvd_in_tail(rng):
    """Paper §6.2 / Fig 1: with slow-ish spectrum decay, default-p R-SVD
    degrades in the tail of the requested triplets while F-SVD stays at
    dense-SVD quality."""
    m, n, rank, r = 300, 300, 100, 60
    A = make_lowrank(rng, m, n, rank)
    s_true = jnp.linalg.svd(A, compute_uv=False)[:r]
    f = fsvd(A, r, 300, host_loop=True)
    rs = rsvd(A, r, p=10)
    err_f = float(jnp.max(jnp.abs(f.s - s_true) / s_true))
    err_r = float(jnp.max(jnp.abs(rs.s - s_true) / s_true))
    assert err_f < 1e-3
    assert err_r > 10 * err_f   # R-SVD default-p visibly worse in the tail


def test_legacy_linop_shims_warn_and_work(rng):
    """The PR-1 shims stay functional but warn with the repo-local
    deprecation category CI escalates to an error (-W error::...), so
    in-repo call sites cannot silently regrow."""
    from repro.compat import ReproDeprecationWarning
    from repro.core.linop import from_dense, from_factors
    A = make_lowrank(rng, 40, 30, 5)
    with pytest.warns(ReproDeprecationWarning):
        op = from_dense(A)
    assert isinstance(op, DenseOp)
    with pytest.warns(ReproDeprecationWarning):
        lo = from_factors(jnp.ones((6, 2)), jnp.ones((2,)),
                          jnp.ones((2, 5)))
    assert isinstance(lo, LowRankOp)


# (m, n) operand, r triplets from k GK iterations: small enough to lower
# in a second, with r distinct from every other width of the program.
HLO_M, HLO_N, HLO_R, HLO_K = 96, 64, 5, 20

SHARDED_SOLVE_TEXT = f"""
    import repro.distributed.gk_dist as gk_dist
    from repro.api import SVDSpec
    from repro.distributed.matvec import sharded_operator
    mesh = jax.make_mesh((4,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    op = sharded_operator(jnp.zeros(({HLO_M}, {HLO_N})), mesh)
    spec = SVDSpec(method="fsvd_sharded", rank={HLO_R}, max_iters={HLO_K})
    text = jax.jit(lambda o, key: gk_dist.solve_fsvd_sharded(
        o, spec, key=key)).lower(op, jax.random.key(0)).as_text()
    print(json.dumps({{"text": text}}))
"""


def f32_products(stablehlo: str) -> list:
    """``(result type, precision)`` of each f32 × f32 ``dot_general`` in
    a lowered program; a product without a precision config runs at the
    default."""
    dot = re.compile(r"stablehlo\.dot_general .*: \(tensor<[\dx]*xf32>, "
                     r"tensor<[\dx]*xf32>\) -> tensor<([\dx]*)xf32>")
    out = []
    for line in stablehlo.splitlines():
        m = dot.search(line)
        if m:
            prec = re.search(r"precision = \[(\w+), (\w+)\]", line)
            out.append((m.group(1), prec.groups() if prec else
                        ("DEFAULT", "DEFAULT")))
    return out


@pytest.mark.parametrize("path", ["DenseOp", "ShardedOp"])
def test_assembly_products_run_at_full_precision(path):
    """V = P G_r and U = A V after the GK loop, and every other f32
    product of the solve, carry ``HIGHEST``: at the default precision a
    TPU rounds f32 operands to bf16 and V leaves A's row space by ~1e-3.
    The CPU computes f32 in full either way, so this reads the program,
    not the numbers: ``fsvd`` on a ``DenseOp``, and ``fsvd_sharded`` on
    four virtual CPU devices."""
    if path == "DenseOp":
        A = jax.ShapeDtypeStruct((HLO_M, HLO_N), jnp.float32)
        text = jax.jit(lambda A, key: fsvd(DenseOp(A), HLO_R, HLO_K,
                                           key=key)).lower(
            A, jax.random.key(0)).as_text()
    else:
        text = run_on_devices(SHARDED_SOLVE_TEXT, 4)["text"]
    products = f32_products(text)
    # the assembly's products are the ones with r columns: V (n, r) and
    # U (m, r), or its row shard
    assembly = [p for p in products if p[0].endswith(f"x{HLO_R}")]
    assert len(assembly) >= 2, products
    assert all(p[1] == ("HIGHEST", "HIGHEST") for p in assembly), assembly
    assert all(p[1] == ("HIGHEST", "HIGHEST") for p in products), products
