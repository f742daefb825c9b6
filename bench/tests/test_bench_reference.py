"""The exact reference, the comparison, and the control that has to fail
it."""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness, reference  # noqa: E402

# Every limits file, of listed cells and of the F-SVD cells that wait
# outside BENCHMARK.json (PERF.md), by the entry its numbers are for.
LIMITS = {p.stem: json.loads(p.read_text())
          for p in sorted((ROOT / "bench" / "limits").glob("*.json"))}
ENTRY = {c: next(e for e, ks in reference.NUMBERS.items() if set(ks) == set(v))
         for c, v in LIMITS.items()}
CELLS = sorted(c for c in LIMITS if ENTRY[c] == "solve")
RANK_CELLS = sorted(c for c in LIMITS if ENTRY[c] == "estimate")


def factors(m=1024, n=512, rank=100, seed=0):
    rng = np.random.default_rng(seed)
    return (rng.standard_normal((m, rank)).astype(np.float32),
            rng.standard_normal((rank, n)).astype(np.float32))


def test_exact_matches_a_dense_svd():
    M, N = factors(300, 200, 20)
    ex = reference.exact(M, N)
    A = M.astype(np.float64) @ N.astype(np.float64)
    U, s, Vt = np.linalg.svd(A, full_matrices=False)
    np.testing.assert_allclose(ex.s, s[:20], rtol=1e-12)
    got = reference.compare(U[:, :5], s[:5], Vt[:5].T, ex, 5)
    assert got["sigma_err"] < 1e-13 and got["out_err"] < 1e-13
    # any orthonormal basis of the subspace reads the same
    got = reference.compare(-U[:, 1:6], s[:5], Vt[:5].T, ex, 5)
    assert got["out_err"] < 1e-13


def test_out_err_reads_the_part_outside_the_subspace():
    M, N = factors(300, 200, 20)
    ex = reference.exact(M, N)
    U, V = ex.Qm[:, :5].copy(), ex.Qn[:, :5].copy()
    rng = np.random.default_rng(1)
    w = rng.standard_normal(300)
    w -= ex.Qm @ (ex.Qm.T @ w)
    U[:, 3] += 1e-3 * w / np.linalg.norm(w)
    got = reference.compare(U, ex.s[:5], V, ex, 5)
    assert got["out_err"] == pytest.approx(1e-3, rel=1e-9)
    assert got["sigma_err"] == 0.0


@pytest.mark.parametrize("bad", ["nan", "short", "wrong_rows"])
def test_bad_answers_read_inf(bad):
    M, N = factors(300, 200, 20)
    ex = reference.exact(M, N)
    U, s, V = ex.Qm[:, :5].copy(), ex.s[:5].copy(), ex.Qn[:, :5].copy()
    if bad == "nan":
        s[2] = np.nan
    elif bad == "short":
        s = s[:4]
    else:
        U = U[:-1]
    assert reference.compare(U, s, V, ex, 5) == {
        "sigma_err": np.inf, "out_err": np.inf}


def exact_ritz(M, N, extra=10):
    """The rank estimate of M·N that the exact spectrum gives: rank R and
    the Ritz values σ², padded as the program pads them."""
    ex = reference.exact(M, N)
    theta = np.concatenate([ex.s ** 2, np.zeros(extra), [-np.inf] * 3])
    return len(ex.s), theta


def test_compare_rank_reads_the_rank_and_every_counted_value():
    M, N = factors(300, 200, 20)
    ex = reference.exact(M, N)
    R, theta = exact_ritz(M, N)
    assert reference.exact_rank(ex) == 20
    assert reference.compare_rank(np.int32(R), theta, ex) == {
        "rank_err": 0.0, "sigma_err": 0.0}
    assert reference.compare_rank(np.int32(R + 1), theta, ex)["rank_err"] == 1
    low = theta.copy()
    low[19] = ex.s[19] ** 2 * (1 - 2e-3) ** 2     # the smallest counted σ
    got = reference.compare_rank(np.int32(R), low, ex)
    assert got["sigma_err"] == pytest.approx(2e-3 * ex.s[19] / ex.s[0])


@pytest.mark.parametrize("bad", ["nan", "short", "negative", "not_scalar"])
def test_bad_rank_estimates_read_inf(bad):
    M, N = factors(300, 200, 20)
    ex = reference.exact(M, N)
    R, theta = exact_ritz(M, N)
    rank = np.int32(R)
    if bad == "nan":
        theta[3] = np.nan
    elif bad == "short":
        theta = theta[:19]
    elif bad == "negative":
        theta[19] = -1.0
    else:
        rank = np.array([R, R])
    assert reference.compare_rank(rank, theta, ex) == {
        "rank_err": np.inf, "sigma_err": np.inf}


def test_rank_control_at_a_small_size():
    """The rank estimate's control, on the CPU, where it reads f32-level
    numbers and the exact rank (on the chip see ``bench/limits``)."""
    M, N = factors()
    ex = reference.exact(M, N)
    rank, theta = reference.control_rank(M @ N, ex.Qn)
    got = reference.compare_rank(rank, theta, ex)
    assert got["rank_err"] == 0 and 1e-9 < got["sigma_err"] < 1e-5


def test_control_at_a_small_size():
    """The control, the reference one precision step below the
    configuration's (``high`` for float32 at ``highest``), in the
    program's place.  On the CPU every f32 product is exact f32 whatever
    the precision asked, so the control reads f32-level numbers here; on
    the chip, at the cells' size, it reads above every cell's limits
    (``upper`` in ``bench/limits``)."""
    M, N = factors()
    ex = reference.exact(M, N)
    U, s, V = (np.asarray(x) for x in reference.control(M @ N, ex.Qn, 20))
    got = reference.compare(U, s, V, ex, 20)
    assert 1e-9 < got["sigma_err"] < 1e-5 and 1e-9 < got["out_err"] < 1e-4


def exact_answer(M, N, r):
    """The top ``r`` singular triplets of M·N in float64."""
    ex = reference.exact(M, N)
    _, Rm = np.linalg.qr(M.astype(np.float64))
    _, Rn = np.linalg.qr(N.T.astype(np.float64))
    Ub, s, Vbt = np.linalg.svd(Rm @ Rn.T)
    return ex.Qm @ Ub[:, :r], s[:r], ex.Qn @ Vbt[:r].T


def one_bf16_pass(x):
    """``x`` as a product that rounds its inputs to bfloat16 leaves it."""
    import jax.numpy as jnp
    return np.asarray(jnp.asarray(x, jnp.float32).astype(jnp.bfloat16)
                      .astype(jnp.float32))


@pytest.mark.parametrize("precision", ["float32", "bfloat16"])
@pytest.mark.parametrize("cell", CELLS)
def test_check_passes_float32_and_fails_a_bf16_pass(cell, precision):
    """Through ``harness.check`` with the cell's committed limits: the
    exact answer rounded to float32 is correct; the same answer whose
    vectors went through one bfloat16 pass, as a product at the default
    precision of a TPU leaves them, is not."""
    M, N = factors()
    U, s, V = exact_answer(M, N, 20)
    if precision == "float32":
        answer = (U.astype(np.float32), s.astype(np.float32),
                  V.astype(np.float32))
    else:
        answer = (one_bf16_pass(U), s.astype(np.float32), one_bf16_pass(V))
    worst, failed = harness.check([answer], M, N, 20, LIMITS[cell])
    assert failed == (precision == "bfloat16"), worst


@pytest.mark.parametrize("precision", ["float32", "bfloat16"])
@pytest.mark.parametrize("cell", RANK_CELLS)
def test_rank_check_passes_float32_and_fails_a_high_pass(cell, precision):
    """Through ``harness.check`` with the cell's committed limits: the
    exact spectrum rounded to float32 is correct; the same spectrum with
    the error a product at ``high`` precision leaves (a relative 2^-16 on
    each σ) is not."""
    M, N = factors()
    R, theta = exact_ritz(M, N)
    s = np.sqrt(np.maximum(theta[:R], 0))
    if precision == "bfloat16":
        s = s * (1 + 2.0 ** -16 * np.where(np.arange(R) % 2, 1, -1))
    answer = (np.int32(R), (s.astype(np.float32) ** 2).astype(np.float32))
    worst, failed = harness.check([answer], M, N, 20, LIMITS[cell],
                                  "estimate")
    assert failed == (precision == "bfloat16"), worst


@pytest.mark.parametrize("cell", CELLS + RANK_CELLS)
def test_limits_sit_between_their_readings(cell):
    for k in reference.NUMBERS[ENTRY[cell]]:
        lim = LIMITS[cell][k]
        if lim.get("exact"):
            assert lim["limit"] == 0
            continue
        assert lim["upper"] >= 3 * lim["lower"], (k, lim)
        assert lim["lower"] < lim["limit"] < lim["upper"], (k, lim)
        # more of the room above the lower reading
        assert lim["limit"] / lim["lower"] >= lim["upper"] / lim["limit"]
