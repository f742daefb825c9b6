"""Sketch-to-SVD solvers: randomized block Krylov and generalized Nyström.

Two points on the accuracy/pass-count frontier the GK family (paper Alg 2)
does not cover:

  * :func:`rbk` — Musco & Musco's randomized **block Krylov**: start from a
    sketched block, expand ``q`` passes of ``Aᵀ(A ·)``, Rayleigh–Ritz
    extract.  Gap-independent accuracy guarantees per pass where plain
    power-iterated R-SVD degrades on clustered spectra; ``q`` interpolates
    between one-shot sketching and the full Krylov accuracy of F-SVD.
  * :func:`gnystrom` — Halko–Martinsson–Tropp / Tropp–Webber's
    **generalized Nyström**: two independent sketches ``AΩ`` / ``ΨᵀA``
    captured in ONE sweep over the operator (the ``Operator.sketch_pass``
    seam), core solve via a stabilized pseudo-inverse.  The only solver in
    the registry that can factorize an operand it may touch exactly once
    (streaming / out-of-core — ``Operator.single_pass_only``).

Both are fully in-graph (jit / vmap-safe — no host round-trips), so they
stage through ``SolverPlan`` and batch through ``solve_batched`` like
``rsvd``; panel orthonormalization is Householder QR (backward-stable
under the cancellation of late Krylov blocks, where one-shot Gram-based
eigQR loses orthonormality like κ²·eps), and the sharded extraction path
reuses ``gk_block``'s psum'd Gram Rayleigh–Ritz so tall factors never
gather.

Test matrices come from :func:`make_sketch` — the sparse-sign ensemble
(ζ nonzeros per column, ±1/√ζ; Clarkson–Woodruff) packed in the static
(d, ζ) ELL layout of ``kernels/sketch_matvec.py``, or a dense Gaussian.
Unlike ``SparseOp``'s value-dependent ELL pack this one is built in-trace
from a PRNG key, so sketched solves survive ``jit`` whole.

Mixed precision follows the house policy (``core/gk.py``): sketch panels
and Krylov bases are *stored* in ``_store_dtype(precision, dtype)`` (bf16
under ``precision="bf16"``), every contraction accumulates in f32.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core._keys import resolve_key
from repro.core.gk import _store_dtype
from repro.core.gk_block import (_block_project, _gram_rayleigh_ritz)
from repro.core.linop import LinOp
from repro.core.operators import (HIGHEST, Operator, as_operator, hdot,
                                  sharding_mesh)
from repro.kernels.sketch_matvec import ZETA

Array = jax.Array

SKETCH_KINDS = ("sparse_sign", "gaussian")

# pseudo-inverse cutoff for the (l, k) Nyström core ΨᵀAΩ, relative to its
# top singular value — below this the core direction is sketch noise and
# inverting it would amplify it into the reconstruction.
_PINV_RCOND = 1e-5


# ---------------------------------------------------------------------------
# test matrices
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class SparseSignSketch:
    """Sparse-sign test matrix T (N, d), ζ nonzeros per column at ±1/√ζ,
    held in the static ELL pack of ``kernels/sketch_matvec``: row i of
    ``idx``/``signs`` lists sketch coordinate i's ζ source rows and signed
    weights.  Coordinates are drawn with replacement (collisions sum —
    consistent between :meth:`dense` scatter and :meth:`tapply` gather).
    """

    idx: Array          # (d, ζ) int32 — source rows of the operand block
    signs: Array        # (d, ζ) — ±1/√ζ in the storage dtype
    n: int              # N, the sketched dimension
    backend: str = "xla"

    @property
    def shape(self) -> tuple[int, int]:
        return (self.n, self.idx.shape[0])

    def dense(self) -> Array:
        """Materialize T (N, d) — the fallback for operators without a
        fused ``sketch_pass`` (panel-sized, never operand-sized)."""
        d = self.idx.shape[0]
        T = jnp.zeros((self.n, d), self.signs.dtype)
        return T.at[self.idx, jnp.arange(d)[:, None]].add(self.signs)

    def tapply(self, X: Array) -> Array:
        """``Tᵀ X`` (d, b) — the matrix-free apply; ``backend="pallas"``
        routes through the gather-only sketch kernel."""
        if self.backend == "pallas":
            from repro.kernels import ops as kops
            return kops.sketch_matmat(self.signs, self.idx, X)
        from repro.kernels import ref
        return ref.sketch_matmat(self.signs, self.idx, X)

    def rapply(self, X: Array) -> Array:
        """``X T`` (b, d) — the operand-side apply: ``A Ω`` without
        forming ``Aᵀ``."""
        if self.backend == "pallas":
            from repro.kernels import ops as kops
            return kops.sketch_matmat(self.signs, self.idx, X, right=True)
        from repro.kernels import ref
        return ref.sketch_matmat(self.signs, self.idx, X.T).T


@dataclasses.dataclass(frozen=True)
class GaussianSketch:
    """Dense N(0, 1) test matrix — the HMT classic; ``tapply`` is a GEMM."""

    T: Array            # (N, d)

    @property
    def shape(self) -> tuple[int, int]:
        return tuple(self.T.shape)

    def dense(self) -> Array:
        return self.T

    def tapply(self, X: Array) -> Array:
        return jnp.dot(self.T.T, X.astype(self.T.dtype), precision=HIGHEST,
                       preferred_element_type=jnp.float32)

    def rapply(self, X: Array) -> Array:
        return jnp.dot(X.astype(self.T.dtype), self.T, precision=HIGHEST,
                       preferred_element_type=jnp.float32)


def make_sketch(key: Array, n: int, d: int, *, kind: str = "sparse_sign",
                zeta: int = ZETA, dtype=jnp.float32, backend: str = "xla"):
    """Draw a (n, d) test matrix of the given ensemble (in-trace)."""
    if kind not in SKETCH_KINDS:
        raise ValueError(
            f"sketch kind must be one of {SKETCH_KINDS}, got {kind!r}")
    if kind == "gaussian":
        return GaussianSketch(jax.random.normal(key, (n, d), jnp.float32)
                              .astype(dtype))
    ki, ks = jax.random.split(key)
    z = max(1, min(zeta, n))
    idx = jax.random.randint(ki, (d, z), 0, n, jnp.int32)
    signs = jax.random.rademacher(ks, (d, z), jnp.float32) / jnp.sqrt(
        jnp.asarray(float(z), jnp.float32))
    return SparseSignSketch(idx, signs.astype(dtype), n, backend=backend)


def nystrom_reconstruct(Y: Array, Zt: Array, C: Array
                        ) -> tuple[Array, Array, Array]:
    """Stabilized generalized-Nyström core solve: the SVD of
    ``Y C⁺ Zt ≈ A`` from the range panel ``Y = AΩ`` (m, k), the co-range
    panel ``Zt = ΨᵀA`` (l, n) and the core ``C = ΨᵀY`` (l, k).

    The core pseudo-inverse is stabilized by an SVD cutoff at
    ``_PINV_RCOND·σmax`` (sketch-noise core directions are dropped, not
    inverted), Y is Householder-QR orthonormalized (backward-stable even
    when the range panel is rank-deficient), and the small projected
    matrix is SVD'd.  Shared by :func:`gnystrom` (fresh one-sweep solve)
    and ``repro.sketchres.reconstruct`` (zero-sweep solve from maintained
    panels).  Returns ``(U (m, k), s (k,), Vt (k, n))`` in f32.
    """
    C = C.astype(jnp.float32)
    Zt = Zt.astype(jnp.float32)
    Uc, sc, Vtc = jnp.linalg.svd(C, full_matrices=False)
    keep = sc > _PINV_RCOND * sc[0]
    sci = jnp.where(keep, 1.0 / jnp.where(keep, sc, 1.0), 0.0)
    M = hdot(Vtc.T * sci[None, :], hdot(Uc.T, Zt))   # (k, n) = C⁺ Zt
    Qy, Ry = jnp.linalg.qr(Y.astype(jnp.float32))
    B = hdot(Ry, M)                               # (k, n) projected core
    Ub, s, Vt = jnp.linalg.svd(B, full_matrices=False)
    return hdot(Qy, Ub), s, Vt


def _panel_dims(r: int, oversample: int, sketch_dim: Optional[int],
                m: int, n: int) -> tuple[int, int]:
    """(k, l): right/left sketch widths for gnystrom — k defaults to the
    R-SVD rule ``r + oversample`` clamped to the small dimension, the
    co-range panel is twice as wide (Tropp's l ≈ 2k recommendation)
    clamped to m, never narrower than k."""
    k = min(sketch_dim or (r + oversample), min(m, n))
    l = max(k, min(2 * k, m))
    return k, l


# ---------------------------------------------------------------------------
# randomized block Krylov (Musco & Musco 2015)
# ---------------------------------------------------------------------------

class SketchSVDResult(NamedTuple):
    U: Array
    s: Array
    V: Array
    passes: Array       # operator sweeps actually spent (0-d int32)


def rbk(
    A: Operator | LinOp | Array,
    r: int,
    *,
    passes: int = 2,
    sketch_dim: Optional[int] = None,
    kind: str = "sparse_sign",
    oversample: int = 10,
    zeta: int = ZETA,
    key: Optional[jax.Array] = None,
    dtype=None,
    precision=None,
    backend: str = "xla",
    callback=None,
) -> SketchSVDResult:
    """Top-r triplets via randomized block Krylov iteration.

    Builds the right-space Krylov basis ``[V₀, (AᵀA)V₀, …, (AᵀA)^q V₀]``
    with V₀ an orthonormalized b-column sketch (no operator touch), each
    expansion CGS-projected against the accumulated basis
    (``_block_project``, f32 accumulation) and re-orthonormalized by
    Householder QR (backward-stable under the heavy cancellation of late
    Krylov blocks — and on a row-sharded mesh the *right*-space basis is
    replicated, so the QR runs replicated with no gather), then
    Rayleigh–Ritz extracts from ``A·basis``.  Operator cost is exactly
    ``2·q_eff + 1`` sweeps (two per expansion, one for extraction);
    ``q_eff`` is the requested ``passes`` statically capped so the basis
    never exceeds ``min(m, n)`` columns — on small operands the basis
    saturates the space and the extraction is (numerically) the exact
    truncated SVD.

    ``precision="bf16"`` stores the accumulated basis half-width; every
    projection/Gram accumulates in f32 (``_block_project``).
    """
    A = as_operator(A)
    m, n = A.shape
    if dtype is None:
        dtype = jnp.promote_types(A.dtype, jnp.float32)
    store = _store_dtype(precision, dtype)
    key = resolve_key(key, caller="rbk")
    b = min(sketch_dim or (r + oversample), min(m, n))
    q_eff = min(max(passes, 0), max((min(m, n) - b) // b, 0))

    om = make_sketch(key, n, b, kind=kind, zeta=zeta, dtype=store,
                     backend=backend)
    block, _ = jnp.linalg.qr(om.dense().astype(jnp.float32))
    basis = block.astype(store)                       # (n, b)
    for _ in range(q_eff):
        W = A.rmatmat(A.matmat(block.astype(store)))  # 2 sweeps
        # full block reorthogonalization: a nearly-converged block leaves
        # a noise-level residual whose QR *normalization* amplifies any
        # surviving basis overlap to O(1) — so project, orthonormalize,
        # then project + orthonormalize once more (the second round sees
        # unit-norm columns and removes the amplified overlap for good).
        W = _block_project(W.astype(jnp.float32), [basis], 2)
        W, _ = jnp.linalg.qr(W)
        W = _block_project(W, [basis], 2)
        block, _ = jnp.linalg.qr(W)
        basis = jnp.concatenate([basis, block.astype(store)], axis=1)

    AV = A.matmat(basis).astype(jnp.float32)          # 1 sweep
    if sharding_mesh(A) is not None:
        # keep the tall factors sharded: d×d Gram + replicated eigh
        U, s, V = _gram_rayleigh_ritz(AV, basis)
    else:
        U, s, Wt = jnp.linalg.svd(AV, full_matrices=False)
        V = basis.astype(jnp.float32) @ Wt.T
    sweeps = jnp.asarray(2 * q_eff + 1, jnp.int32)
    if callback is not None:
        from repro.api.callbacks import ConvergenceInfo
        callback.on_info(ConvergenceInfo(
            jnp.zeros((0,), jnp.float32), sweeps,
            jnp.asarray(False), method="rbk"))
    return SketchSVDResult(U[:, :r], s[:r], V[:, :r], sweeps)


# ---------------------------------------------------------------------------
# generalized Nyström (HMT 2011 §5.5 / Tropp–Webber)
# ---------------------------------------------------------------------------

def gnystrom(
    A: Operator | LinOp | Array,
    r: int,
    *,
    sketch_dim: Optional[int] = None,
    kind: str = "sparse_sign",
    oversample: int = 10,
    zeta: int = ZETA,
    key: Optional[jax.Array] = None,
    dtype=None,
    precision=None,
    backend: str = "xla",
    callback=None,
) -> SketchSVDResult:
    """Top-r triplets from ONE sweep over the operator.

    Draws independent test matrices Ω (n, k) and Ψ (m, l), captures
    ``Y = AΩ`` and ``Z = AᵀΨ`` in a single :meth:`Operator.sketch_pass`,
    and reconstructs ``A ≈ Y (ΨᵀY)⁺ (ΨᵀA)`` — the generalized Nyström
    approximation.  Everything after the sweep touches only the panels:
    the (l, k) core ``ΨᵀY`` comes from ``Ψ.tapply(Y)``, its pseudo-inverse
    is stabilized by an SVD cutoff at ``1e-5·σmax`` (sketch-noise core
    directions are dropped, not inverted), Y is QR-orthonormalized and
    the small projected matrix SVD'd.

    This is the breaker's shed solver in the serving layer and the
    resolution target for ``Operator.single_pass_only`` operands.
    """
    A = as_operator(A)
    m, n = A.shape
    if dtype is None:
        dtype = jnp.promote_types(A.dtype, jnp.float32)
    store = _store_dtype(precision, dtype)
    key = resolve_key(key, caller="gnystrom")
    k, l = _panel_dims(r, oversample, sketch_dim, m, n)
    ko, kp = jax.random.split(key)
    om = make_sketch(ko, n, k, kind=kind, zeta=zeta, dtype=store,
                     backend=backend)
    ps = make_sketch(kp, m, l, kind=kind, zeta=zeta, dtype=store,
                     backend=backend)

    Y, Z = A.sketch_pass(om, ps)                  # THE one operator sweep
    Y = Y.astype(store)                           # (m, k) range panel
    Zt = Z.astype(jnp.float32).T                  # (l, n) = ΨᵀA
    C = ps.tapply(Y).astype(jnp.float32)          # (l, k) = ΨᵀAΩ, no touch
    U, s, Vt = nystrom_reconstruct(Y, Zt, C)
    if callback is not None:
        from repro.api.callbacks import ConvergenceInfo
        callback.on_info(ConvergenceInfo(
            jnp.zeros((0,), jnp.float32), jnp.asarray(1, jnp.int32),
            jnp.asarray(False), method="gnystrom"))
    return SketchSVDResult(U[:, :r], s[:r], Vt[:r, :].T,
                           jnp.asarray(1, jnp.int32))
