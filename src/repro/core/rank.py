"""Algorithm 3 — fast numerical rank determination.

Run GK bidiagonalization with the breakdown criterion (Alg 1); the iteration
count at breakdown is the *first* rank estimate; the *accurate* rank is the
number of eigenvalues of B^T B above epsilon (Alg 3 line 4).
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

import repro.core.gk as gk_mod
from repro.core.linop import LinOp
from repro.core.operators import (GramOp, Operator, TransposedOp, as_operator)
from repro.core.tridiag import btb_eigh
from repro.runtime.spans import span

Array = jax.Array


class RankResult(NamedTuple):
    rank: Array          # () int32 — accurate numerical rank (Alg 3)
    gk_iterations: Array  # () int32 — Alg 1 iteration count at termination
    eigenvalues: Array   # (k,) Ritz values of B^T B, descending (−inf padded)


def numerical_rank(
    A: Operator | LinOp | Array,
    *,
    max_iters: Optional[int] = None,
    eps: float = 1e-8,
    relative_eps: bool = True,
    sigma_tol: Optional[float] = None,
    key: Optional[jax.Array] = None,
    host_loop: bool = True,
    reorth_passes: int = 2,
    dtype=None,
) -> RankResult:
    """Estimate rank(A).

    ``eps`` is the breakdown threshold of Alg 1.  ``sigma_tol`` is the Alg-3
    counting threshold applied to the Ritz values of B^T B; it defaults to a
    spectrum-relative tolerance ``(max theta) * tol_dtype`` which is the
    float32-safe reading of the paper's absolute 1e-8 (the paper ran float64
    NumPy where absolute thresholds are meaningful).
    """
    A = as_operator(A)
    # Matrix-free unwrapping: rank(Aᵀ) == rank(A) and rank(AᵀA) ==
    # rank(AAᵀ) == rank(A), so run GK on the innermost operand — never on
    # the composed chain (GramOp matvecs square the condition number,
    # σ(AᵀA) = σ(A)², which pushes small-but-nonzero singular values under
    # the breakdown threshold and *under*-counts rank; a TransposedOp adds
    # an indirection per half-iteration for no information).  Neither wrapper
    # is ever densified.  For a GramOp input the returned ``eigenvalues``
    # are therefore the Ritz values of the *inner* operator's BᵀB — the
    # rank they count is identical.
    while isinstance(A, (TransposedOp, GramOp)):
        A = A.inner
        A = as_operator(A)
    if max_iters is None:
        max_iters = min(A.shape)
    max_iters = min(max_iters, min(A.shape))
    runner = gk_mod.gk_bidiag_host if host_loop else gk_mod.gk_bidiag
    res = runner(A, max_iters, key=key, eps=eps, relative_eps=relative_eps,
                 reorth_passes=reorth_passes, dtype=dtype)
    with span("repro.rank.count"):
        theta, _ = btb_eigh(res.alphas, res.betas, res.kprime)
        finite = jnp.where(jnp.isfinite(theta), theta, 0.0)
        if sigma_tol is None:
            big = jnp.max(finite)
            eps_dt = jnp.finfo(finite.dtype).eps
            # theta ~ sigma^2: tolerance on the squared scale, with
            # generous headroom over roundoff accumulated across k' Lanczos
            # steps.
            sigma_tol_arr = (big * eps_dt
                             * res.kprime.astype(finite.dtype) * 10.0)
        else:
            sigma_tol_arr = jnp.asarray(sigma_tol, finite.dtype)
        rank = jnp.sum(finite > sigma_tol_arr).astype(jnp.int32)
    return RankResult(rank, res.kprime, theta)
