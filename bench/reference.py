"""Plain reference for a low-rank operand, the comparison that decides
``correct``, and the lower-precision control.

The operand is A = M N with M (m, R) and N (R, n).  Thin QRs M = Qm Rm and
Nᵀ = Qn Rn give A = Qm (Rm Rnᵀ) Qnᵀ: the singular values of A are those
of the R × R core Rm Rnᵀ, and Qm, Qn span its column and row spaces.  The
reference computes them on the host in float64 with NumPy from M and N
alone; it imports nothing of the program under test and needs no m × n
work.

Two numbers are compared for each checked solve, over its top r triplets:

* ``sigma_err`` = max_i |ŝ_i − σ_i| / σ_1;
* ``out_err`` = max_i max(‖(I − Qm Qmᵀ) û_i‖, ‖(I − Qn Qnᵀ) v̂_i‖): how
  far a returned singular vector leaves the exact singular subspace of A
  (Qm, Qn span its column and row space).

Two for each checked rank estimate (its rank and the Ritz values θ of
BᵀB that it counts):

* ``rank_err`` = |rank − R|, R the exact rank of M·N (an exact
  comparison);
* ``sigma_err`` = max_{i<R} |√θ_i − σ_i| / σ_1 over all R singular values
  the rank counts.

The rotation of the vectors inside that subspace is not compared: its
accuracy is set by the GK breakdown threshold, 40 eps_f32 ‖A‖, which is
the error of a product at ``high`` precision too, so no limit tells a
sound run from the control there (see PERF.md).

A missing, short or non-finite answer reads ``inf``.
"""
from __future__ import annotations

from typing import NamedTuple

import numpy as np

NUMBERS = {"solve": ("sigma_err", "out_err"),
           "estimate": ("rank_err", "sigma_err")}
RANK_TOL = 1e-3     # the control's rank: σ above this share of σ_1


class Exact(NamedTuple):
    s: np.ndarray       # (R,)  all nonzero singular values, descending
    Qm: np.ndarray      # (m, R) orthonormal basis of A's column space
    Qn: np.ndarray      # (n, R) orthonormal basis of A's row space


def exact(M, N) -> Exact:
    """Singular values and singular subspaces of M·N, float64, on the
    host."""
    M = np.asarray(M, np.float64)
    N = np.asarray(N, np.float64)
    Qm, Rm = np.linalg.qr(M)
    Qn, Rn = np.linalg.qr(N.T)
    s = np.linalg.svd(Rm @ Rn.T, compute_uv=False)
    return Exact(s, Qm, Qn)


def _leave(X, Q):
    """Column norms of the part of X outside span(Q)."""
    return np.linalg.norm(X - Q @ (Q.T @ X), axis=0)


def compare(U, s, V, ex: Exact, r: int) -> dict:
    """``{"sigma_err": ..., "out_err": ...}`` of one answer's top ``r``
    triplets against :func:`exact`."""
    U = np.asarray(U, np.float64)
    s = np.asarray(s, np.float64).ravel()
    V = np.asarray(V, np.float64)
    bad = {k: float("inf") for k in NUMBERS["solve"]}
    if (s.shape[0] < r or U.ndim != 2 or V.ndim != 2
            or U.shape[0] != ex.Qm.shape[0] or V.shape[0] != ex.Qn.shape[0]
            or U.shape[1] < r or V.shape[1] < r):
        return bad
    s, U, V = s[:r], U[:, :r], V[:, :r]
    if not (np.isfinite(s).all() and np.isfinite(U).all()
            and np.isfinite(V).all()):
        return bad
    sigma_err = float(np.max(np.abs(s - ex.s[:r])) / ex.s[0])
    out_err = float(max(_leave(U, ex.Qm).max(), _leave(V, ex.Qn).max()))
    return {"sigma_err": sigma_err, "out_err": out_err}


def exact_rank(ex: Exact) -> int:
    """The rank of M·N: its singular values above float64 round-off."""
    return int(np.sum(ex.s > ex.s[0] * 1e-10))


def compare_rank(rank, theta, ex: Exact) -> dict:
    """``{"rank_err": ..., "sigma_err": ...}`` of one rank estimate, its
    ``rank`` and the Ritz values ``theta`` (σ², descending, padded with
    −inf), against :func:`exact`."""
    R = exact_rank(ex)
    theta = np.asarray(theta, np.float64).ravel()
    bad = {k: float("inf") for k in NUMBERS["estimate"]}
    if np.ndim(rank) != 0 or theta.shape[0] < R:
        return bad
    top = theta[:R]
    if not (np.isfinite(top).all() and (top >= 0).all()):
        return bad
    return {"rank_err": float(abs(int(rank) - R)),
            "sigma_err": float(np.max(np.abs(np.sqrt(top) - ex.s[:R]))
                               / ex.s[0])}


def _dot_high(a, b):
    """``a @ b`` at the ``high`` precision of an f32 matmul: three bf16
    passes (hi·hi + hi·lo + lo·hi, f32 accumulation), spelled out so that
    every backend rounds alike."""
    import jax.numpy as jnp
    f32, bf16 = jnp.float32, jnp.bfloat16
    ah = a.astype(bf16)
    al = (a - ah.astype(f32)).astype(bf16)
    bh = b.astype(bf16)
    bl = (b - bh.astype(f32)).astype(bf16)

    def d(x, y):
        return jnp.dot(x, y, preferred_element_type=f32)
    return d(ah, bh) + (d(ah, bl) + d(al, bh))


def control(A, Qn, r: int):
    """The reference put in the program's place, one precision step below
    what the configuration states (float32 at ``highest``): it reads A as
    the program does, projecting it on the exact row space, Y = A Qn, and
    takes the SVD of Y, with every product at ``high`` and the
    factorizations under ``high`` matmul precision.  Returns ``(U, s, V)``
    device arrays."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def run(A, Qn):
        Y = _dot_high(A, Qn)
        with jax.default_matmul_precision("high"):
            Qy, Ry = jnp.linalg.qr(Y)
            Ub, s, Vbt = jnp.linalg.svd(Ry)
        return (_dot_high(Qy, Ub[:, :r]), s[:r],
                _dot_high(Qn, Vbt[:r].T))
    return run(A, jnp.asarray(Qn, jnp.float32))


def control_rank(A, Qn):
    """The control of a rank estimate: the singular values of
    :func:`control` over the whole exact row space, as Ritz values σ², and
    the rank as the count of them above round-off (``RANK_TOL`` σ_1).
    Returns ``(rank, theta)`` on the host."""
    _, s, _ = control(A, Qn, np.shape(Qn)[1])
    s = np.asarray(s, np.float64)
    return np.int32(np.sum(s > RANK_TOL * s[0])), s ** 2
