"""Algorithm 2 — Accurate and fast partial SVD (F-SVD).

Pipeline (paper Alg 2):
  1. GK-bidiagonalize A for (at most) k iterations -> B_{k'+1,k'}, P_{k'}, Q.
  2. eigh of the small tridiagonal B^T B -> Ritz pairs (theta_i, g_i).
  3. Right singular vectors  V = P @ g   (Ritz vectors of A^T A).
  4. sigma = sqrt(theta);  U = A V Sigma^{-1}   (line 7).

Only matvecs with A are ever needed, so the same code serves dense matrices,
implicitly-factored operators and pod-sharded operators.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

import repro.core.gk as gk_mod
from repro.core.linop import LinOp
from repro.core.operators import DenseOp, Operator, as_operator, hdot
from repro.core.tridiag import btb_eigh

Array = jax.Array


class FSVDResult(NamedTuple):
    U: Array        # (m, r)
    s: Array        # (r,)    descending
    V: Array        # (n, r)
    kprime: Array   # () int32 — GK iterations actually used (rank estimate)
    breakdown: Array


def _mixed_matmul(B: Array, X: Array) -> Array:
    """``B @ X`` at full f32 precision, or with f32 accumulation when B is
    a narrow-storage basis (bf16 B stays bf16 in memory; X is rounded to
    B's dtype at the MXU).  At the default precision a TPU rounds f32
    operands to bf16, which moves V out of A's row space by ~1e-3."""
    if B.dtype == X.dtype:
        return hdot(B, X)
    return jnp.dot(B, X.astype(B.dtype), preferred_element_type=jnp.float32)


def _assemble(op, res: gk_mod.GKResult, r: int) -> FSVDResult:
    theta, G = btb_eigh(res.alphas, res.betas, res.kprime)
    r = min(r, res.alphas.shape[0])
    theta_r = theta[:r]
    G_r = G[:, :r]
    # padding Ritz values were masked to -inf; clamp for sqrt and zero the
    # corresponding singular values.
    pad = ~jnp.isfinite(theta_r)
    s = jnp.sqrt(jnp.clip(jnp.where(pad, 0.0, theta_r), 0.0, None))
    V = _mixed_matmul(res.P, G_r)                       # line 3: V2 = P V1
    AV = op.matmat(V)                                   # lines 6-8
    U = AV / jnp.where(s > 0, s, 1.0)[None, :]
    U = jnp.where(pad[None, :], 0.0, U)
    V = jnp.where(pad[None, :], 0.0, V)
    return FSVDResult(U, s, V, res.kprime, res.breakdown)


def default_k(r: int, shape) -> int:
    """The Krylov budget a cold F-SVD uses when ``k`` is omitted:
    ``min(4 r, min(m, n))`` — the space needs slack beyond r for the top-r
    Ritz values to converge.  Shared with the session layer, whose refine
    cap must track what cold solves actually run."""
    return min(4 * r, min(shape))


def fsvd(
    A: Operator | LinOp | Array,
    r: int,
    k: Optional[int] = None,
    *,
    key: Optional[jax.Array] = None,
    q1: Optional[Array] = None,
    eps: float = 1e-8,
    relative_eps: bool = True,
    reorth_passes: int = 2,
    host_loop: bool = False,
    dtype=None,
    precision=None,
    callback=None,
) -> FSVDResult:
    """Top-r singular triplets of A via k-step GK bidiagonalization.

    ``k`` defaults to ``min(4 r, min(m, n))`` — the Krylov space needs some
    slack beyond r for the top-r Ritz values to converge (paper uses e.g.
    k=550 for r=100).  ``host_loop=True`` uses the early-exit host loop.
    ``precision="bf16"`` stores the Lanczos bases half-width (see
    :func:`repro.core.gk.gk_bidiag`); the Ritz extraction stays f32.
    ``callback`` is a ``repro.api.callbacks.ConvergenceCallback``: host-loop
    runs get ``on_step`` per iteration, every run gets the final
    ``on_info`` (in-graph: a pytree of device arrays / tracers).
    """
    A = as_operator(A)
    if k is None:
        k = default_k(r, A.shape)
    k = max(k, r)
    runner = gk_mod.gk_bidiag_host if host_loop else gk_mod.gk_bidiag
    res = runner(A, k, key=key, q1=q1, eps=eps, relative_eps=relative_eps,
                 reorth_passes=reorth_passes, dtype=dtype,
                 precision=precision, callback=callback)
    return _assemble(A, res, r)


def fsvd_dense_reconstruct(out: FSVDResult) -> Array:
    """U diag(s) V^T (tests / retraction materialization)."""
    return (out.U * out.s[None, :]) @ out.V.T


def truncated_svd_errors(A: Operator | LinOp | Array, out) -> dict:
    """The paper's Table-2 error metrics for a computed partial SVD.

    ``out`` is any (U, s, V, ...) result — FSVDResult, RSVDResult or an
    ``repro.api`` Factorization.
    """
    Aop = as_operator(A)
    dense = Aop.A if isinstance(Aop, DenseOp) else None
    # relative error: ||A^T U - V Sigma||_F / ||Sigma||_F
    ATU = Aop.rmatmat(out.U)
    rel = jnp.linalg.norm(ATU - out.V * out.s[None, :]) / jnp.linalg.norm(out.s)
    res = None
    if dense is not None:
        res = jnp.linalg.norm(dense - fsvd_dense_reconstruct(out))
    return {"relative": rel, "residual": res}
