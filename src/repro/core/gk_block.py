"""Block Golub-Kahan bidiagonalization (beyond-paper TPU adaptation).

The paper's Alg 1 advances one Lanczos vector per pass over A: arithmetic
intensity ~1 FLOP/byte — hopeless against a 197 TFLOP/s MXU behind
819 GB/s of HBM.  The block variant advances ``b`` vectors per pass:

    A P_j   : (m, n) @ (n, b)   — intensity ~b FLOP/byte
    Aᵀ Q_j  : same on the way back

so b = 128-256 turns the GK loop from bandwidth-bound GEMV streaming into
MXU-shaped GEMM streaming (the Pallas matvec kernels in ``repro.kernels``
then apply with the vector dimension widened to b).  The projected matrix
is block-bidiagonal; its small dense SVD gives Ritz triplets exactly as in
Alg 2.  Convergence per *iteration* is faster than vector Lanczos (each
step captures a b-dimensional Krylov slab) at the cost of b× more flops
per step — on TPU those flops are nearly free, which is the whole trade.

Used as an alternative backend for F-SVD (``fsvd_block``) and validated
against dense SVD + the vector path in ``tests/test_gk_block.py``.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core._keys import resolve_key
from repro.core.linop import LinOp
from repro.core.operators import (HIGHEST, Operator, as_operator, hdot,
                                  sharding_mesh)

Array = jax.Array


class BlockGKResult(NamedTuple):
    Q: Array          # (m, (s+1) b) left basis
    P: Array          # (n, s b) right basis
    K: Array          # ((s+1) b, s b) projected block-bidiagonal Qᵀ A P
    steps: int        # completed block steps s
    breakdown: bool


def _reorth(W: Array, basis: Array, passes: int) -> Array:
    for _ in range(passes):
        W = W - hdot(basis, hdot(basis.T, W))
    return W


def gk_block_host(
    op: Operator | LinOp | Array,
    block: int,
    steps: int,
    *,
    key: Optional[jax.Array] = None,
    eps: float = 1e-6,
    reorth_passes: int = 2,
) -> BlockGKResult:
    """Host-loop block bidiagonalization with full block reorthogonalization.

    Recurrences (block analogue of paper eq. 7-8):
        P_1 A_1ᵀ            = QR(Aᵀ Q_1)
        Q_{j+1} B_{j+1}     = QR(A P_j − Q_j A_j)
        P_{j+1} A_{j+1}ᵀ    = QR(Aᵀ Q_{j+1} − P_j B_{j+1}ᵀ)
    K = Qᵀ A P is block-bidiagonal with diagonal blocks A_j and subdiagonal
    blocks B_{j+1}.
    """
    op = as_operator(op)
    m, n = op.shape
    b = min(block, m, n)
    steps = min(steps, max(min(m, n) // b, 1))
    key = resolve_key(key, caller="gk_block_host")

    Q1, _ = jnp.linalg.qr(jax.random.normal(key, (m, b), jnp.float32))
    Z = op.rmatmat(Q1).astype(jnp.float32)               # (n, b)
    P1, A1t = jnp.linalg.qr(Z)
    Qs, Ps = [Q1], [P1]
    Adiag = [A1t.T]                                      # A_1 (b, b)
    Bsub: list[Array] = []
    Qmat, Pmat = Q1, P1
    scale = float(jnp.linalg.norm(A1t)) + 1e-30
    breakdown = False

    for j in range(1, steps):
        W = op.matmat(Ps[-1]).astype(jnp.float32) - hdot(Qs[-1], Adiag[-1])
        W = _reorth(W, Qmat, reorth_passes)
        Qj, Bj = jnp.linalg.qr(W)
        if float(jnp.linalg.norm(Bj)) < eps * scale:
            breakdown = True
            break
        Z = op.rmatmat(Qj).astype(jnp.float32) - hdot(Ps[-1], Bj.T)
        Z = _reorth(Z, Pmat, reorth_passes)
        Pj, Ajt = jnp.linalg.qr(Z)
        if float(jnp.linalg.norm(Ajt)) < eps * scale:
            Qs.append(Qj)
            Bsub.append(Bj)
            Qmat = jnp.concatenate([Qmat, Qj], axis=1)
            breakdown = True
            break
        Qs.append(Qj)
        Ps.append(Pj)
        Adiag.append(Ajt.T)
        Bsub.append(Bj)
        Qmat = jnp.concatenate([Qmat, Qj], axis=1)
        Pmat = jnp.concatenate([Pmat, Pj], axis=1)

    s = len(Ps)
    K = jnp.zeros((Qmat.shape[1], Pmat.shape[1]), jnp.float32)
    for j in range(s):
        K = K.at[j * b:(j + 1) * b, j * b:(j + 1) * b].set(Adiag[j])
    for j, Bj in enumerate(Bsub[:Qmat.shape[1] // b - 1]):
        K = K.at[(j + 1) * b:(j + 2) * b, j * b:(j + 1) * b].set(Bj)
    return BlockGKResult(Qmat, Pmat, K, s, breakdown)


class FSVDBlockResult(NamedTuple):
    U: Array
    s: Array
    V: Array
    steps: int
    breakdown: bool


def fsvd_block(
    A: Operator | LinOp | Array,
    r: int,
    *,
    block: Optional[int] = None,
    steps: Optional[int] = None,
    key: Optional[jax.Array] = None,
    reorth_passes: int = 2,
) -> FSVDBlockResult:
    """Top-r singular triplets via block GK (Alg 2 with a block backend).

    ``block`` defaults to an MXU-friendly width ≥ r; ``steps`` to enough
    slab captures for the top-r Ritz values to converge.
    """
    A = as_operator(A)
    m, n = A.shape
    if block is None:
        block = min(max(r, 32), min(m, n))
    if steps is None:
        steps = max(min(min(m, n) // block, max(2, 3 * r // block + 2)), 1)
    res = gk_block_host(A, block, steps, key=key,
                        reorth_passes=reorth_passes)
    Uk, sk, Vkt = jnp.linalg.svd(res.K, full_matrices=False)
    r = min(r, sk.shape[0])
    U = res.Q @ Uk[:, :r]
    V = res.P @ Vkt[:r].T
    return FSVDBlockResult(U, sk[:r], V, res.steps, res.breakdown)


# ---------------------------------------------------------------------------
# Streaming blocked GK with locking + thick restart (memory-budgeted)
# ---------------------------------------------------------------------------

class BlockedFSVDResult(NamedTuple):
    U: Array          # (m, r)
    s: Array          # (r,)    descending
    V: Array          # (n, r)
    restarts: int     # restart cycles consumed
    block_passes: int # streaming passes over A (block matvec round trips)
    converged: bool   # did r Ritz pairs lock before the restart budget?


def _orth_against(W: Array, bases, passes: int) -> Array:
    for _ in range(passes):
        for B in bases:
            if B.shape[1]:
                W = W - hdot(B, hdot(B.T, W))
    return W


# a column whose norm drops by this factor under orthogonalization carries
# no new direction (f32 CGS2 noise floor), only roundoff — keeping it (or
# letting Householder QR substitute an arbitrary completion, which is NOT
# orthogonal to the deflation spaces) destroys basis orthonormality and
# with it the Ritz-value bound sigma_ritz <= sigma_max.
_MGS_DROP = 1e-5


def _mgs_block(W: Array, bases, passes: int = 2,
               drop: float = _MGS_DROP) -> Array:
    """Rank-revealing block orthonormalization (host-side MGS).

    Orthonormalizes W's columns against every basis in ``bases`` and each
    other, *dropping* columns that lose all their mass instead of
    completing them arbitrarily.  Returns (n, k≤W.cols) in f32; k == 0
    means W carried no direction outside the spans.  ``drop`` is the
    survival threshold — callers with narrow-storage (bf16) bases raise it
    to that storage's orthogonalization noise floor, since a spanned
    column can retain ~eps_bf16 of its mass against a rounded basis.
    """
    live = [B for B in bases if B.shape[1]]
    compute = jnp.promote_types(W.dtype, jnp.float32)
    cols: list[Array] = []
    for j in range(W.shape[1]):
        v = W[:, j].astype(compute)
        nv0 = float(jnp.linalg.norm(v))
        if nv0 == 0.0:
            continue
        for _ in range(passes):
            for B in live:
                v = v - hdot(B, hdot(B.T, v))
            for c in cols:
                v = v - c * jnp.vdot(c, v)
        nv = float(jnp.linalg.norm(v))
        if nv > drop * nv0:
            cols.append(v / nv)
    if not cols:
        return jnp.zeros((W.shape[0], 0), compute)
    return jnp.stack(cols, axis=1)


def _block_project(W: Array, bases, passes: int) -> Array:
    """``W − Σ B (Bᵀ W)``, ``passes`` times — blocked CGS against every
    basis with f32 accumulation (narrow-storage bases stay narrow)."""
    for _ in range(passes):
        for B in bases:
            if B.shape[1]:
                C = jnp.dot(B.T, W.astype(B.dtype), precision=HIGHEST,
                            preferred_element_type=jnp.float32) \
                    if B.dtype != W.dtype else hdot(B.T, W)
                W = W - (jnp.dot(B, C.astype(B.dtype), precision=HIGHEST,
                                 preferred_element_type=jnp.float32)
                         if B.dtype != W.dtype else hdot(B, C))
    return W


# the Gram route resolves column mass only down to ~sqrt(eps) of the block
# scale (eigenvalues of WᵀW carry eps·λ_max absolute noise), so its drop
# floor sits at the CholQR/eigQR limit rather than the per-column MGS one.
_GRAM_DROP = 4e-4


def _mgs_block_gram(W: Array, bases, passes: int = 2,
                    drop: float = _MGS_DROP) -> Array:
    """Distributed drop-in for :func:`_mgs_block`: blocked projection plus
    rank-revealing orthonormalization via the psum'd Gram matrix.

    The per-column host MGS syncs a scalar per column per block — fine on
    one device, a mesh-wide stall at scale.  Here every reduction is a
    *block* contraction (``BᵀW``, ``WᵀW``): on sharded operands GSPMD
    lowers each to one local GEMM + one psum.  Rank revelation comes from
    ``eigh(WᵀW)``: directions with ``sqrt(λ) ≤ drop · max‖w_j‖`` carry no
    direction outside the spans (Gram-resolution noise) and are dropped,
    never completed arbitrarily — the same contract as ``_mgs_block``.  A
    second project+eigh pass restores orthogonality to working precision
    (single-pass eigQR degrades as cond², the CholQR2 fix).
    """
    compute = jnp.promote_types(W.dtype, jnp.float32)
    W = W.astype(compute)
    live = [B for B in bases if B.shape[1]]
    eff_drop = max(drop, _GRAM_DROP)
    for _ in range(2):                      # project + eigQR, twice
        if W.shape[1] == 0:
            return jnp.zeros((W.shape[0], 0), compute)
        # the drop threshold is relative to THIS pass's input columns
        # (matching _mgs_block's post-vs-pre column-norm test); the second
        # pass sees unit columns, so a stale first-pass scale would
        # spuriously drop everything whenever the raw block is large.
        scale = float(jnp.max(jnp.linalg.norm(W, axis=0)))
        W = _block_project(W, live, passes)
        G = hdot(W.T, W)
        lam, E = jnp.linalg.eigh(G)         # ascending
        lam = np.asarray(jnp.sqrt(jnp.clip(lam, 0.0, None)))
        keep = np.nonzero(lam > eff_drop * max(scale, 1e-30))[0]
        if keep.size == 0:
            return jnp.zeros((W.shape[0], 0), compute)
        W = (W @ E[:, keep]) / jnp.asarray(lam[keep], compute)[None, :]
    return W


def _gram_rayleigh_ritz(AV: Array, basis: Array
                        ) -> tuple[Array, Array, Array]:
    """Ritz triplets of span(basis) from the psum'd (d, d) Gram matrix.

    ``svd(AV)`` on a row-sharded (m, d) block would gather the tall factor
    to one device; instead ``H = (AV)ᵀAV`` reduces to a replicated d×d
    problem (one local GEMM + one psum under GSPMD), ``eigh(H)`` runs
    replicated, and the big factors stay sharded: ``U = AV W Σ⁻¹`` is a
    local GEMM on the row shards.  Returns (U, s, V) with s descending.
    """
    H = hdot(AV.T, AV)                                  # (d, d) replicated
    theta, W = jnp.linalg.eigh(H)                       # ascending
    theta = theta[::-1]
    W = W[:, ::-1]
    s = jnp.sqrt(jnp.clip(theta, 0.0, None))
    U = (AV @ W) / jnp.where(s > 0, s, 1.0)[None, :]
    V = basis.astype(jnp.float32) @ W
    return U, s, V


def fsvd_blocked(
    A: Operator | LinOp | Array,
    r: int,
    *,
    block: Optional[int] = None,
    max_basis: Optional[int] = None,
    tol: float = 1e-8,
    relative_tol: bool = True,
    max_restarts: int = 40,
    key: Optional[jax.Array] = None,
    q1: Optional[Array] = None,
    reorth_passes: int = 2,
    dtype=None,
    precision: Optional[str] = None,
    callback=None,
) -> BlockedFSVDResult:
    """Top-r singular triplets by streaming block GK under a memory budget.

    The basis never exceeds ``max_basis`` right vectors: each cycle expands
    a block-Krylov chain ``P_{j+1} = orth(Aᵀ(A P_j))`` (the GK alternation,
    fused — only n-vectors are retained), Rayleigh–Ritz extracts candidate
    triplets from the accumulated span, pairs whose residual
    ``‖Aᵀu − σv‖ ≤ tol·σ_max`` are *locked* (deflated from all later
    cycles), and the basis restarts *thick* — re-seeded with the best
    unconverged Ritz vectors, so no Krylov information is thrown away.

    This is the Musco–Musco block-Krylov scheme with LOBPCG-style soft
    locking; A is touched only through block matvecs, so operators whose
    dense form would not fit memory (``SparseOp``, ``KroneckerOp``, pod-
    sharded) stream through unchanged.  Sharded operands additionally get
    the distributed stages: the block expansion runs row-sharded, the
    orthonormalization is blocked MGS via psum'd Gram matrices (no
    per-column device syncs), and Rayleigh-Ritz runs replicated on the
    small projected Gram problem — the (m, ·) factors never gather.

    ``relative_tol=True`` (default) scales the residual threshold by the
    running ``σ_max`` estimate with ``tol`` clamped to the dtype's Lanczos
    noise floor (same policy as ``core.gk``) — the paper's 1e-8 default
    remains meaningful in f64 and degrades gracefully to ~2e-5 in f32;
    ``relative_tol=False`` uses ``tol`` as an absolute residual bound.
    ``q1`` (an m-vector) warm-starts the first block via ``Aᵀq1``.
    ``precision="bf16"`` stores the retained bases (the memory-budgeted
    part) half-width; every expansion, orthogonalization and Rayleigh-Ritz
    extraction still accumulates in the compute dtype, and the locking
    threshold / MGS drop floor widen to the storage's noise floor.
    ``callback`` (``repro.api.callbacks.ConvergenceCallback``) gets
    ``on_step(cycle, residual=..., locked=...)`` per restart cycle — host
    scalars this loop computes anyway — and a final ``on_info`` whose
    residual trace is the per-cycle minimum Ritz residual.
    """
    from repro.core.gk import _store_dtype
    A = as_operator(A)
    # sharded operands swap the two dense-friendly stages for distributed
    # forms: per-column host MGS -> blocked psum'd-Gram orthonormalization
    # (no per-column device syncs), and svd(AV) -> replicated Rayleigh-Ritz
    # on the small projected Gram problem (the (m, d) factor stays
    # row-sharded end to end).
    distributed = sharding_mesh(A) is not None
    orth_block = _mgs_block_gram if distributed else _mgs_block
    m, n = A.shape
    r = min(r, min(m, n))
    b = block if block is not None else min(max(8, min(r, 32)), min(m, n))
    b = max(min(b, min(m, n)), 1)
    if max_basis is None:
        max_basis = min(min(m, n), max(3 * r, r + 2 * b))
    max_basis = min(max(max_basis, r + b, 2 * b), min(m, n))
    if dtype is None:
        dtype = jnp.promote_types(A.dtype, jnp.float32)
    store = _store_dtype(precision, dtype)
    store_eps = float(jnp.finfo(store).eps)
    mgs_drop = max(_MGS_DROP, 8.0 * store_eps)
    eff_tol = max(tol, 200.0 * float(jnp.finfo(dtype).eps), 8.0 * store_eps)

    if q1 is None:
        key = resolve_key(key, caller="fsvd_blocked")
    else:
        key = key if key is not None else jax.random.PRNGKey(0)

    locked_V = jnp.zeros((n, 0), store)
    locked_U = jnp.zeros((m, 0), store)
    locked_s: list[float] = []

    key, k0 = jax.random.split(key)
    V = jax.random.normal(k0, (n, b), dtype)
    if q1 is not None:
        V = V.at[:, 0].set(A.rmv(q1.astype(dtype)))
    V = jnp.linalg.qr(V)[0]

    block_passes = 0
    restarts = 0
    converged = False
    sigma_max = 0.0
    cycle_res: list[float] = []             # per-cycle min Ritz residual
    Us = S = Vr = None                      # last Rayleigh-Ritz extraction

    for restart in range(max_restarts):
        restarts = restart + 1
        # --- expand the Krylov chain under the basis budget --------------
        # the seed block is capped one short of the budget so at least one
        # A(ᵀ)A application always fits: with zero applications the span
        # never grows and restarts would stagnate on the same subspace.
        budget = max_basis - locked_V.shape[1]
        if budget >= 2:
            V = V[:, :min(V.shape[1], budget - 1)]
        else:
            V = V[:, :max(budget, 1)]
        basis = orth_block(V, (locked_V,), reorth_passes,
                           drop=mgs_drop).astype(store)
        if basis.shape[1] == 0:
            key, kf = jax.random.split(key)
            basis = orth_block(jax.random.normal(kf, (n, min(b, budget)),
                                                 dtype),
                               (locked_V,), reorth_passes,
                               drop=mgs_drop).astype(store)
        last = basis
        while basis.shape[1] < budget and last.shape[1]:
            W = A.rmatmat(A.matmat(last)).astype(dtype)   # GK round trip
            block_passes += 1
            Qb = orth_block(W, (locked_V, basis), reorth_passes,
                            drop=mgs_drop)
            if Qb.shape[1] == 0:
                # chain exhausted the reachable subspace — refresh randomly
                key, kf = jax.random.split(key)
                Qb = orth_block(
                    jax.random.normal(kf, (n, last.shape[1]), dtype),
                    (locked_V, basis), reorth_passes, drop=mgs_drop)
                if Qb.shape[1] == 0:
                    break                     # whole space is spanned
            Qb = Qb[:, :budget - basis.shape[1]].astype(store)
            basis = jnp.concatenate([basis, Qb], axis=1)
            last = Qb
        # --- Rayleigh-Ritz on span(basis), deflated against locked -------
        AV = A.matmat(basis).astype(dtype)                # (m, d), d ≤ budget
        block_passes += 1
        if distributed:
            Us, S, Vr = _gram_rayleigh_ritz(AV, basis)
        else:
            Us, S, Wt = jnp.linalg.svd(AV, full_matrices=False)
            Vr = basis @ Wt.T
        sigma_max = max(sigma_max,
                        float(S[0]) if S.shape[0] else 0.0,
                        locked_s[0] if locked_s else 0.0)
        # residuals ‖Aᵀu_i − σ_i v_i‖ decide locking
        Rres = A.rmatmat(Us).astype(dtype) - Vr * S[None, :]
        resn = jnp.linalg.norm(Rres, axis=0)
        thresh = eff_tol * max(sigma_max, 1.0) if relative_tol else tol
        need = r - len(locked_s)
        lock_idx = []
        for i in range(S.shape[0]):
            if len(lock_idx) >= need:
                break
            if float(resn[i]) <= thresh:
                lock_idx.append(i)
            else:
                break          # lock a contiguous head: keeps order strict
        if lock_idx:
            sel = jnp.asarray(lock_idx)
            newV = _orth_against(Vr[:, sel], (locked_V,), 1)
            newV = newV / jnp.linalg.norm(newV, axis=0, keepdims=True)
            locked_V = jnp.concatenate([locked_V, newV.astype(store)],
                                       axis=1)
            locked_U = jnp.concatenate(
                [locked_U, Us[:, sel].astype(store)], axis=1)
            locked_s.extend(float(S[i]) for i in lock_idx)
        cycle_res.append(float(jnp.min(resn)) if S.shape[0] else 0.0)
        if callback is not None:
            callback.on_step(restart, residual=cycle_res[-1],
                             locked=len(locked_s))
        if len(locked_s) >= r:
            converged = True
            break
        # --- thick restart: best unconverged Ritz vectors seed the next
        # cycle (orthonormalized against the locked pairs at loop top) ---
        rest = [i for i in range(S.shape[0]) if i not in set(lock_idx)]
        keep = rest[:max(b, min(r - len(locked_s), len(rest)))]
        if keep:
            V = Vr[:, jnp.asarray(keep)]
        else:
            key, kf = jax.random.split(key)
            V = jax.random.normal(kf, (n, b), dtype)

    # --- assemble: locked pairs first, fill from the last extraction -----
    if len(locked_s) < r and S is not None:
        fill = r - len(locked_s)
        # take the best remaining Ritz pairs not yet locked
        taken = 0
        cols_u, cols_v, vals = [], [], []
        for i in range(S.shape[0]):
            if taken >= fill:
                break
            v_i = Vr[:, i]
            if locked_V.shape[1] and float(
                    jnp.max(jnp.abs(locked_V.T @ v_i))) > 0.5:
                continue       # this Ritz pair is (a copy of) a locked one
            cols_u.append(Us[:, i])
            cols_v.append(v_i)
            vals.append(float(S[i]))
            taken += 1
        if cols_u:
            locked_U = jnp.concatenate(
                [locked_U, jnp.stack(cols_u, axis=1).astype(store)], axis=1)
            locked_V = jnp.concatenate(
                [locked_V, jnp.stack(cols_v, axis=1).astype(store)], axis=1)
            locked_s.extend(vals)

    s_arr = jnp.asarray(locked_s, dtype)
    order = jnp.argsort(-s_arr)
    U = locked_U[:, order]
    V_out = locked_V[:, order]
    s_arr = s_arr[order]
    if s_arr.shape[0] < r:                      # exhausted rank-deficient A
        pad = r - s_arr.shape[0]
        U = jnp.concatenate([U, jnp.zeros((m, pad), store)], axis=1)
        V_out = jnp.concatenate([V_out, jnp.zeros((n, pad), store)], axis=1)
        s_arr = jnp.concatenate([s_arr, jnp.zeros((pad,), dtype)])
    if callback is not None:
        from repro.api.callbacks import ConvergenceInfo
        callback.on_info(ConvergenceInfo(
            jnp.asarray(cycle_res, jnp.float32),
            jnp.asarray(block_passes, jnp.int32),
            jnp.asarray(not converged), method="fsvd_blocked"))
    return BlockedFSVDResult(U[:, :r], s_arr[:r], V_out[:, :r],
                             restarts, block_passes, converged)
