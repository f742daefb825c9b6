"""Rank-k update / downdate of an existing factorization — zero Krylov
iterations.

The tracking and serving stacks (``Session``, ``repro.serve``) follow
operators that drift *structurally*: ``A' = beta * A + Delta`` with
``Delta`` low-rank (a momentum-style state update, a batch of new rows, a
rank-1 similarity edit).  A refine solve still runs a (reduced) GK
recurrence over the full operator; but when the drift itself is rank-k,
the drifted factorization is computable *exactly* from the previous one
(Brand's SVD update; Halko–Martinsson–Tropp / Tropp–Webber in PAPERS.md)
with no matvecs against ``A'`` at all:

    A' = beta * U diag(s) Vt + C Dt          (C: (m, k), D: (n, k))

  1. project the delta factors onto/off the current bases:
     ``UtC = Ut C``, ``Qc Rc = qr((I − U Ut) C)`` (CGS-reorthogonalized),
     and symmetrically for D against V;
  2. assemble the small dense (r+k, r+k) core
     ``K = beta * diag(s ⊕ 0) + [UtC; Rc] [VtD; Rd]^T``;
  3. SVD the core and rotate the augmented bases
     ``U' = [U | Qc] Uk``, ``V' = [V | Qd] Vk``; truncate back to r.

Cost is ``O((m + n)(r + k)^2)`` — independent of the GK iteration count
and of ``min(m, n)`` beyond the thin-QR — which is why the update path is
the serving stack's biggest latency lever (``benchmarks/update_bench.py``).
The result is *exact* when the previous factorization captured the
operator exactly (rank-r operand); for noisy operands the unabsorbed tail
shows up in the residual, which is what ``Session``'s update gate
measures.

Downdating removes rows or columns: zeroing rows ``S`` of the *factored*
operator is itself a rank-|S| update ``Delta = −1_S (U[S] diag(s) Vt)``
derived from the factorization alone, so the same core routine serves
both directions.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.api.results import Factorization
from repro.core.operators import LowRankOp, cgs, hdot

Array = jax.Array


def delta_rank(delta: LowRankOp) -> int:
    """Total factored rank of a ``LowRankOp`` (main triplet + extras)."""
    k = delta.s.shape[0]
    for L, _ in delta.extra:
        k += L.shape[1]
    return k


def delta_factors(delta: LowRankOp, compute=jnp.float32
                  ) -> tuple[Array, Array]:
    """``(C, D)`` with ``Delta = C @ D.T`` — the op's ``scale`` and ``s``
    fold into C so D stays orthonormal-friendly."""
    Cs = [delta.U.astype(compute) * delta.s.astype(compute)[None, :]]
    Ds = [delta.Vt.T.astype(compute)]
    for L, R in delta.extra:
        Cs.append(L.astype(compute))
        Ds.append(R.T.astype(compute))
    C = Cs[0] if len(Cs) == 1 else jnp.concatenate(Cs, axis=1)
    D = Ds[0] if len(Ds) == 1 else jnp.concatenate(Ds, axis=1)
    return delta.scale * C, D


def _core_outer(Chat: Array, Dhat: Array, backend: str) -> Array:
    """``Chat @ Dhat.T`` for the (r+k, r+k) dense core.  On the Pallas
    backend this reuses the low-rank materialization kernel (a single
    (r+k, k) x (k, r+k) tile); XLA composes a plain GEMM."""
    if backend == "pallas":
        from repro.kernels import ops as kops
        rk = Chat.shape[0]
        ones = jnp.ones((Chat.shape[1],), Chat.dtype)
        return kops.lowrank_matmul(Chat, ones, Dhat.T, bm=rk, bn=rk)
    return hdot(Chat, Dhat.T)


def update_factorization(fact: Factorization, delta: LowRankOp, *,
                         beta=1.0, rank: Optional[int] = None,
                         passes: int = 2,
                         backend: str = "xla") -> Factorization:
    """Factorization of ``beta * (U diag(s) Vt) + delta`` — no GK.

    ``rank=None`` keeps the previous rank (the tracking contract); any
    ``rank <= fact.rank + delta_rank(delta)`` is valid.  ``beta`` may be a
    traced scalar, so one staged executable covers every decay factor.
    The returned ``Factorization`` has ``iterations == 0`` and
    ``method == "update"``.
    """
    compute = jnp.promote_types(fact.U.dtype, jnp.float32)
    U = fact.U.astype(compute)
    V = fact.V.astype(compute)
    s = fact.s.astype(compute)
    C, D = delta_factors(delta, compute)
    r = s.shape[0]
    k = C.shape[1]
    if rank is None:
        rank = r
    rank = min(int(rank), r + k)

    # split each delta factor into its component in the current basis and
    # an orthonormal complement (CGS^passes keeps the complement clean
    # even when the delta nearly lies in the tracked subspace).
    UtC = hdot(U.T, C)
    Qc, Rc = jnp.linalg.qr(cgs(C, U, passes))
    VtD = hdot(V.T, D)
    Qd, Rd = jnp.linalg.qr(cgs(D, V, passes))

    Chat = jnp.concatenate([UtC, Rc], axis=0)          # (r+k, k)
    Dhat = jnp.concatenate([VtD, Rd], axis=0)          # (r+k, k)
    pad = jnp.zeros((k,), compute)
    K = beta * jnp.diag(jnp.concatenate([s, pad])) \
        + _core_outer(Chat, Dhat, backend)
    Uk, sk, Vkt = jnp.linalg.svd(K.astype(compute), full_matrices=False)

    U2 = hdot(jnp.concatenate([U, Qc], axis=1), Uk[:, :rank])
    V2 = hdot(jnp.concatenate([V, Qd], axis=1), Vkt[:rank, :].T)
    return Factorization(U2.astype(fact.U.dtype),
                         sk[:rank].astype(fact.s.dtype),
                         V2.astype(fact.V.dtype),
                         iterations=jnp.zeros((), jnp.int32),
                         breakdown=jnp.zeros((), bool),
                         method="update")


# ---------------------------------------------------------------------------
# downdates: row / column removal as self-derived low-rank deltas
# ---------------------------------------------------------------------------

def row_removal_delta(fact: Factorization, rows) -> LowRankOp:
    """The rank-|rows| delta that zeroes ``rows`` of the factored
    operator: ``Delta = −1_rows (U[rows] diag(s) Vt)``."""
    compute = jnp.promote_types(fact.U.dtype, jnp.float32)
    rows = jnp.asarray(rows, jnp.int32)
    m = fact.U.shape[0]
    C = -jax.nn.one_hot(rows, m, dtype=compute).T             # (m, j)
    Vt = (fact.U[rows, :].astype(compute)
          * fact.s.astype(compute)[None, :]) @ fact.V.T.astype(compute)
    return LowRankOp(C, jnp.ones((rows.shape[0],), compute), Vt)


def col_removal_delta(fact: Factorization, cols) -> LowRankOp:
    """The rank-|cols| delta that zeroes ``cols`` of the factored
    operator: ``Delta = −(U diag(s) Vt e_cols) e_cols^T``."""
    compute = jnp.promote_types(fact.U.dtype, jnp.float32)
    cols = jnp.asarray(cols, jnp.int32)
    n = fact.V.shape[0]
    U = -(fact.U.astype(compute)
          * fact.s.astype(compute)[None, :]) @ fact.V[cols, :].T.astype(
              compute)                                         # (m, j)
    Vt = jax.nn.one_hot(cols, n, dtype=compute)                # (j, n)
    return LowRankOp(U, jnp.ones((cols.shape[0],), compute), Vt)


def downdate_rows(fact: Factorization, rows, *, passes: int = 2,
                  backend: str = "xla") -> Factorization:
    """Factorization of the operator with ``rows`` removed (zeroed).
    Exact when ``fact`` is: removing rows cannot raise the rank, so the
    truncation back to r loses nothing."""
    return update_factorization(fact, row_removal_delta(fact, rows),
                                passes=passes, backend=backend)


def downdate_cols(fact: Factorization, cols, *, passes: int = 2,
                  backend: str = "xla") -> Factorization:
    """Factorization of the operator with ``cols`` removed (zeroed)."""
    return update_factorization(fact, col_removal_delta(fact, cols),
                                passes=passes, backend=backend)


def materialize_lowrank(delta: LowRankOp, *, backend: str = "xla",
                        dtype=None) -> Array:
    """Densify a ``LowRankOp`` (for folding a drift into a dense operand).

    The Pallas backend routes the main triplet through the
    output-stationary materialization kernel; extras and the scale compose
    on top.
    """
    if backend == "pallas":
        from repro.kernels import ops as kops
        W = kops.lowrank_matmul(delta.U, delta.s, delta.Vt)
    else:
        W = (delta.U * delta.s[None, :]) @ delta.Vt
    for L, R in delta.extra:
        W = W + L @ R
    W = delta.scale * W
    return W if dtype is None else W.astype(dtype)


__all__ = ["col_removal_delta", "delta_factors", "delta_rank",
           "downdate_cols", "downdate_rows", "materialize_lowrank",
           "row_removal_delta", "update_factorization"]
