"""Pytree-registered operator algebra.

The paper's algorithms touch A only through ``A @ p`` / ``A.T @ q``; the seed
expressed that as closure-based :class:`~repro.core.linop.LinOp` objects,
which work but cannot cross ``jit`` / ``vmap`` / ``shard_map`` boundaries
(closures are not pytrees).  This module replaces them with small
dataclass operators whose array fields are pytree leaves:

  * ``DenseOp(A, backend=...)``    — in-memory matrix; ``backend="pallas"``
    routes the fused Lanczos matvecs through ``repro.kernels`` (subsumes the
    old ``from_dense(use_kernels=True)`` flag).
  * ``LowRankOp(U, s, Vt, extra=..., scale=...)`` — ``scale * (U diag(s) Vt
    + Σ L_i R_i)`` never materialized (the RSL gradient / retraction
    operand).
  * ``SumOp``, ``ScaledOp``, ``TransposedOp`` — closure of the algebra under
    ``A + B``, ``alpha * A`` and ``A.T``.
  * ``SparseOp(data, indices, spshape)`` — BCOO-backed sparse matrix;
    ``backend="pallas"`` routes matvecs through the row-blocked ELL kernel
    in ``repro.kernels.sparse_matvec`` (build via ``SparseOp.fromdense`` /
    ``SparseOp.from_bcoo`` so the ELL pack is precomputed).
  * ``KroneckerOp(a, b)`` — ``a ⊗ b`` applied through the reshape identity
    ``(A ⊗ B) vec(X) = vec(A X Bᵀ)``; the product is never materialized.
  * ``GramOp(inner, side)`` — ``AᵀA`` / ``AAᵀ`` as an operator (rank
    estimation / normal-equation solves without forming the Gram matrix).

Because operators are pytrees, ``jax.vmap(factorize_impl)`` over a stacked
``DenseOp`` yields a batched partial SVD with no extra code, and a sharded
operator (``repro.distributed.ShardedOp``) threads through ``jit`` whole.

All operators satisfy the same duck protocol as ``LinOp`` (``shape``,
``dtype``, ``mv``, ``rmv``, ``mv_fused``, ``rmv_fused``, ``matmat``,
``rmatmat``) so the GK / F-SVD / rank cores run unchanged on either.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Tuple

import jax
import jax.numpy as jnp

from repro.runtime.spans import span

Array = jax.Array

_BACKENDS = ("xla", "pallas")

# f32 products on the solver path ask for full f32 (multi-pass bf16 on the
# TPU MXU).  At DEFAULT precision the TPU rounds both operands to bf16,
# which breaks CGS orthogonality and the breakdown-based rank test; the
# GEMVs are HBM-bound, so the extra MXU passes cost no wall time there.
HIGHEST = jax.lax.Precision.HIGHEST


def hdot(a: Array, b: Array) -> Array:
    """``a @ b`` at full f32 precision (see ``HIGHEST``)."""
    return jnp.matmul(a, b, precision=HIGHEST)


def cgs(v: Array, basis: Array, passes: int) -> Array:
    """Classical Gram-Schmidt of ``v`` against the (zero-padded) basis
    columns, ``passes`` times, with f32 accumulation.

    When the basis is stored in a narrower dtype than ``v`` (the
    mixed-precision bf16 policy), the products run with bf16 operands and
    f32 accumulation (``preferred_element_type``) — the basis is never
    upcast in memory, which is the whole point of storing it half-width.
    For matching dtypes this is exactly ``v − B (Bᵀ v)``, bit-for-bit.
    """
    if basis.dtype == v.dtype:
        for _ in range(passes):
            v = v - hdot(basis, hdot(basis.T, v))
        return v
    for _ in range(passes):
        c = jnp.dot(basis.T, v.astype(basis.dtype), precision=HIGHEST,
                    preferred_element_type=jnp.float32)
        v = v - jnp.dot(basis, c.astype(basis.dtype), precision=HIGHEST,
                        preferred_element_type=jnp.float32)
    return v


def register_operator(cls):
    """Register an operator dataclass as a pytree.

    ``_data_fields`` become children (traced/vmapped/sharded);
    ``_meta_fields`` become static aux data (must be hashable).  Unflatten
    bypasses no logic — constructors must stay dumb so tree transforms can
    pass placeholders.  Extensions (e.g. ``repro.distributed.ShardedOp``)
    use this too.
    """
    data = cls._data_fields
    meta = cls._meta_fields

    def flatten(op):
        return (tuple(getattr(op, f) for f in data),
                tuple(getattr(op, f) for f in meta))

    def unflatten(aux, children):
        kw = dict(zip(data, children))
        kw.update(zip(meta, aux))
        return cls(**kw)

    jax.tree_util.register_pytree_node(cls, flatten, unflatten)
    return cls


class Operator:
    """Base class: linear-map protocol + algebra sugar.

    Subclasses define ``shape``, ``dtype``, ``mv``, ``rmv`` and may override
    the fused three-term forms, the block forms and ``T`` with cheaper
    specializations.
    """

    _data_fields: Tuple[str, ...] = ()
    _meta_fields: Tuple[str, ...] = ()

    # Streaming hint: True means the operand can only afford ONE sweep
    # (out-of-core / streamed once) — ``resolve_method`` routes such
    # operands to the single-pass ``gnystrom`` solver.
    single_pass_only: bool = False

    # --- protocol -----------------------------------------------------
    @property
    def shape(self) -> tuple[int, int]:
        raise NotImplementedError

    @property
    def dtype(self):
        raise NotImplementedError

    @property
    def m(self) -> int:
        return self.shape[0]

    @property
    def n(self) -> int:
        return self.shape[1]

    def mv(self, p: Array) -> Array:
        raise NotImplementedError

    def rmv(self, q: Array) -> Array:
        raise NotImplementedError

    def mv_fused(self, p: Array, y: Array, alpha) -> Array:
        """Lanczos three-term form ``A p − alpha y``."""
        return self.mv(p) - alpha * y

    def rmv_fused(self, q: Array, y: Array, beta) -> Array:
        return self.rmv(q) - beta * y

    def lanczos_step(self, p: Array, y: Array, alpha, basis: Array, *,
                     passes: int = 2) -> tuple[Array, Array]:
        """One fused left GK half-step: ``u = A p − α y`` reorthogonalized
        CGS^passes against ``basis``, plus its norm → ``(u, ‖u‖)``.

        The default composes the fused matvec with :func:`cgs`; operators
        with a single-pass pipeline (``DenseOp(backend="pallas")``)
        override it with the ``kernels.gk_step`` kernels.
        """
        with span("repro.op.matvec"):
            u = self.mv_fused(p, y, alpha)
        with span("repro.op.cgs"):
            u = cgs(u, basis, passes)
        return u, jnp.linalg.norm(u)

    def lanczos_rstep(self, q: Array, y: Array, beta, basis: Array, *,
                      passes: int = 2) -> tuple[Array, Array]:
        """Right GK half-step: ``v = Aᵀ q − β y`` vs ``basis`` → (v, ‖v‖)."""
        with span("repro.op.matvec"):
            v = self.rmv_fused(q, y, beta)
        with span("repro.op.cgs"):
            v = cgs(v, basis, passes)
        return v, jnp.linalg.norm(v)

    def matmat(self, V: Array) -> Array:
        return jax.vmap(self.mv, in_axes=1, out_axes=1)(V)

    def rmatmat(self, Q: Array) -> Array:
        return jax.vmap(self.rmv, in_axes=1, out_axes=1)(Q)

    def sketch_pass(self, omega, psi) -> tuple[Array, Array]:
        """ONE sweep over the operator capturing both sketch directions:
        ``(A Ω, Aᵀ Ψ)`` for test matrices Ω (n, k) and Ψ (m, l) from
        ``repro.core.sketch`` — the single-pass seam ``gnystrom`` builds
        on (and the unit the pass-budget guards count as one touch).

        The default composes the block forms on the densified panels;
        operators with a fused path (``DenseOp(backend="pallas")`` via the
        sparse-sign sketch kernel, ``ShardedOp`` via one shard_map body
        with a single psum) override it.
        """
        return self.matmat(omega.dense()), self.rmatmat(psi.dense())

    def to_dense(self) -> Array:
        return self.matmat(jnp.eye(self.n, dtype=self.dtype))

    # --- algebra ------------------------------------------------------
    @property
    def T(self) -> "Operator":
        return TransposedOp(self)

    def __matmul__(self, x):
        if isinstance(x, Operator):
            return NotImplemented
        x = jnp.asarray(x)
        return self.mv(x) if x.ndim == 1 else self.matmat(x)

    def _check_same_shape(self, other: "Operator"):
        if tuple(self.shape) != tuple(other.shape):
            raise ValueError(
                f"operator shapes disagree: {tuple(self.shape)} + "
                f"{tuple(other.shape)}")
        return other

    def __add__(self, other):
        return SumOp((self, self._check_same_shape(as_operator(other))))

    def __radd__(self, other):
        return SumOp((self._check_same_shape(as_operator(other)), self))

    def __sub__(self, other):
        return SumOp((self, ScaledOp(
            -1.0, self._check_same_shape(as_operator(other)))))

    def __mul__(self, alpha):
        return ScaledOp(alpha, self)

    __rmul__ = __mul__

    def __neg__(self):
        return ScaledOp(-1.0, self)


@register_operator
@dataclasses.dataclass(frozen=True, eq=False)
class DenseOp(Operator):
    """In-memory (m, n) matrix.  ``backend="pallas"`` backs the fused
    Lanczos matvecs with the single-pass Pallas kernels (A streamed through
    VMEM once per half-iteration); ``"xla"`` composes plain GEMVs."""

    A: Array
    backend: str = "xla"

    _data_fields = ("A",)
    _meta_fields = ("backend",)

    @property
    def shape(self) -> tuple[int, int]:
        return tuple(self.A.shape)

    @property
    def dtype(self):
        return self.A.dtype

    def mv(self, p):
        return hdot(self.A, p)

    def rmv(self, q):
        return hdot(self.A.T, q)

    def mv_fused(self, p, y, alpha):
        if self.backend == "pallas":
            from repro.kernels import ops as kops
            return kops.matvec_fused(self.A, p, y, alpha)
        return hdot(self.A, p) - alpha * y

    def rmv_fused(self, q, y, beta):
        if self.backend == "pallas":
            from repro.kernels import ops as kops
            return kops.rmatvec_fused(self.A, q, y, beta)
        return hdot(self.A.T, q) - beta * y

    def lanczos_step(self, p, y, alpha, basis, *, passes=2):
        if self.backend == "pallas" and self.A.dtype != jnp.float64:
            from repro.kernels import ops as kops
            # the fused kernel sweeps A and reorthogonalizes in one pass
            with span("repro.op.matvec"):
                return kops.gk_step_fused(self.A, p, y, alpha, basis, passes)
        return Operator.lanczos_step(self, p, y, alpha, basis,
                                     passes=passes)

    def lanczos_rstep(self, q, y, beta, basis, *, passes=2):
        if self.backend == "pallas" and self.A.dtype != jnp.float64:
            from repro.kernels import ops as kops
            with span("repro.op.matvec"):
                return kops.gk_rstep_fused(self.A, q, y, beta, basis,
                                           passes)
        return Operator.lanczos_rstep(self, q, y, beta, basis,
                                      passes=passes)

    def matmat(self, V):
        return hdot(self.A, V)

    def rmatmat(self, Q):
        return hdot(self.A.T, Q)

    def sketch_pass(self, omega, psi):
        if self.backend == "pallas":
            # both directions through the sketch kernel, each reading A
            # untransposed: A Ω directly, and Aᵀ Ψ = (Ψᵀ A)ᵀ.
            return omega.rapply(self.A), psi.tapply(self.A).T
        return hdot(self.A, omega.dense()), hdot(self.A.T, psi.dense())

    def to_dense(self):
        return self.A

    @property
    def T(self):
        return DenseOp(self.A.T, backend=self.backend)


@register_operator
@dataclasses.dataclass(frozen=True, eq=False)
class LowRankOp(Operator):
    """``scale * (U diag(s) Vt + Σ_i L_i R_i)`` — never materialized.

    ``extra`` is a tuple of (L_i (m, k_i), R_i (k_i, n)) addend factor pairs;
    this expresses e.g. ``W − eta Z`` (manifold point minus tangent step) or
    the RSL batch gradient ``X_bᵀ diag(c) V_b + wd · W``.
    """

    U: Array                      # (m, r)
    s: Array                      # (r,)
    Vt: Array                     # (r, n)
    extra: Tuple[Tuple[Array, Array], ...] = ()
    scale: Any = 1.0              # python scalar or 0-d array (leaf)

    _data_fields = ("U", "s", "Vt", "extra", "scale")
    _meta_fields = ()

    @property
    def shape(self) -> tuple[int, int]:
        return (self.U.shape[0], self.Vt.shape[1])

    @property
    def dtype(self):
        return self.U.dtype

    def mv(self, p):
        y = self.U @ (self.s * (self.Vt @ p))
        for L, R in self.extra:
            y = y + L @ (R @ p)
        return self.scale * y

    def rmv(self, q):
        y = self.Vt.T @ (self.s * (self.U.T @ q))
        for L, R in self.extra:
            y = y + R.T @ (L.T @ q)
        return self.scale * y

    def matmat(self, V):
        y = self.U @ (self.s[:, None] * (self.Vt @ V))
        for L, R in self.extra:
            y = y + L @ (R @ V)
        return self.scale * y

    def rmatmat(self, Q):
        y = self.Vt.T @ (self.s[:, None] * (self.U.T @ Q))
        for L, R in self.extra:
            y = y + R.T @ (L.T @ Q)
        return self.scale * y

    @property
    def T(self):
        return LowRankOp(self.Vt.T, self.s, self.U.T,
                         extra=tuple((R.T, L.T) for L, R in self.extra),
                         scale=self.scale)


@register_operator
@dataclasses.dataclass(frozen=True, eq=False)
class SumOp(Operator):
    """A + B (+ ...): matvecs distribute over the terms."""

    terms: Tuple[Operator, ...]

    _data_fields = ("terms",)
    _meta_fields = ()

    @property
    def shape(self) -> tuple[int, int]:
        return self.terms[0].shape

    @property
    def dtype(self):
        return jnp.result_type(*(t.dtype for t in self.terms))

    def mv(self, p):
        y = self.terms[0].mv(p)
        for t in self.terms[1:]:
            y = y + t.mv(p)
        return y

    def rmv(self, q):
        y = self.terms[0].rmv(q)
        for t in self.terms[1:]:
            y = y + t.rmv(q)
        return y

    def matmat(self, V):
        y = self.terms[0].matmat(V)
        for t in self.terms[1:]:
            y = y + t.matmat(V)
        return y

    def rmatmat(self, Q):
        y = self.terms[0].rmatmat(Q)
        for t in self.terms[1:]:
            y = y + t.rmatmat(Q)
        return y

    @property
    def T(self):
        return SumOp(tuple(t.T for t in self.terms))

    def __add__(self, other):     # flatten nested sums
        other = self._check_same_shape(as_operator(other))
        more = other.terms if isinstance(other, SumOp) else (other,)
        return SumOp(self.terms + more)


@register_operator
@dataclasses.dataclass(frozen=True, eq=False)
class ScaledOp(Operator):
    """alpha * A (alpha a scalar leaf — may be traced)."""

    alpha: Any
    op: Operator

    _data_fields = ("alpha", "op")
    _meta_fields = ()

    @property
    def shape(self) -> tuple[int, int]:
        return self.op.shape

    @property
    def dtype(self):
        return self.op.dtype

    def mv(self, p):
        return self.alpha * self.op.mv(p)

    def rmv(self, q):
        return self.alpha * self.op.rmv(q)

    def matmat(self, V):
        return self.alpha * self.op.matmat(V)

    def rmatmat(self, Q):
        return self.alpha * self.op.rmatmat(Q)

    @property
    def T(self):
        return ScaledOp(self.alpha, self.op.T)

    def __mul__(self, a):
        return ScaledOp(a * self.alpha, self.op)

    __rmul__ = __mul__


@register_operator
@dataclasses.dataclass(frozen=True, eq=False)
class TransposedOp(Operator):
    """A.T for operators without a cheaper specialized transpose."""

    inner: Operator

    _data_fields = ("inner",)
    _meta_fields = ()

    @property
    def shape(self) -> tuple[int, int]:
        m, n = self.inner.shape
        return (n, m)

    @property
    def dtype(self):
        return self.inner.dtype

    def mv(self, p):
        return self.inner.rmv(p)

    def rmv(self, q):
        return self.inner.mv(q)

    def mv_fused(self, p, y, alpha):
        return self.inner.rmv_fused(p, y, alpha)

    def rmv_fused(self, q, y, beta):
        return self.inner.mv_fused(q, y, beta)

    def lanczos_step(self, p, y, alpha, basis, *, passes=2):
        # Aᵀ's left half-step is A's right half-step: inherit the inner
        # operator's fused pipeline (Pallas tiles, sharded stacked-psum)
        # instead of falling back to the generic matvec + CGS composition.
        return self.inner.lanczos_rstep(p, y, alpha, basis, passes=passes)

    def lanczos_rstep(self, q, y, beta, basis, *, passes=2):
        return self.inner.lanczos_step(q, y, beta, basis, passes=passes)

    def matmat(self, V):
        return self.inner.rmatmat(V)

    def rmatmat(self, Q):
        return self.inner.matmat(Q)

    @property
    def T(self):
        return self.inner


@register_operator
@dataclasses.dataclass(frozen=True, eq=False)
class SparseOp(Operator):
    """Sparse (m, n) matrix in COO triplet form — never densified on the
    solver path (the GK / F-SVD / rank cores only ever ask for matvecs).

    ``data`` (nnz,) and ``indices`` (nnz, 2) follow the BCOO convention
    (duplicate coordinates sum); ``spshape`` is static so the operator
    survives tracing (a traced ``indices`` cannot carry the shape).

    ``backend="pallas"`` routes matvecs through the row-blocked ELL kernel
    (``repro.kernels.sparse_matvec``); the ELL pack is precomputed from
    concrete coordinates by :meth:`fromdense` / :meth:`from_bcoo` /
    :meth:`from_coo` (its row widths are value-dependent, so it cannot be
    built under a trace) and rides along as pytree leaves.  ``backend="xla"``
    uses BCOO dot-general.
    """

    data: Array                   # (nnz,)
    indices: Array                # (nnz, 2) int — [row, col]
    spshape: Tuple[int, int] = (0, 0)
    ell: Any = None               # ((m,L) vals, (m,L) cols, (n,L') vals,
                                  #  (n,L') rows) — pallas pack, or None
    backend: str = "xla"

    _data_fields = ("data", "indices", "ell")
    _meta_fields = ("spshape", "backend")

    # --- constructors -------------------------------------------------
    @classmethod
    def fromdense(cls, A, *, backend: str = "xla", nse=None) -> "SparseOp":
        from jax.experimental import sparse as jsparse
        return cls.from_bcoo(jsparse.BCOO.fromdense(jnp.asarray(A), nse=nse),
                             backend=backend)

    @classmethod
    def from_bcoo(cls, mat, *, backend: str = "xla") -> "SparseOp":
        return cls.from_coo(mat.data, mat.indices, tuple(mat.shape),
                            backend=backend)

    @classmethod
    def from_coo(cls, data, indices, spshape, *,
                 backend: str = "xla") -> "SparseOp":
        if backend not in _BACKENDS:
            raise ValueError(
                f"backend must be one of {_BACKENDS}, got {backend!r}")
        data = jnp.asarray(data)
        indices = jnp.asarray(indices)
        ell = None
        if backend == "pallas":
            from repro.kernels.sparse_matvec import ell_pack
            ell = (ell_pack(data, indices, spshape)
                   + ell_pack(data, indices[:, ::-1], spshape[::-1]))
        return cls(data, indices, tuple(spshape), ell=ell, backend=backend)

    # --- protocol -----------------------------------------------------
    @property
    def shape(self) -> tuple[int, int]:
        return tuple(self.spshape)

    @property
    def dtype(self):
        return self.data.dtype

    @property
    def nnz(self) -> int:
        return self.data.shape[0]

    @property
    def density(self) -> float:
        m, n = self.spshape
        return self.nnz / max(m * n, 1)

    def _bcoo(self):
        from jax.experimental import sparse as jsparse
        return jsparse.BCOO((self.data, self.indices), shape=self.spshape)

    def mv(self, p):
        if self.backend == "pallas" and self.ell is not None:
            from repro.kernels import ops as kops
            return kops.sparse_matvec(self.ell[0], self.ell[1], p)
        return self._bcoo() @ p

    def rmv(self, q):
        if self.backend == "pallas" and self.ell is not None:
            from repro.kernels import ops as kops
            return kops.sparse_matvec(self.ell[2], self.ell[3], q)
        return self.T._bcoo() @ q

    def matmat(self, V):
        if self.backend == "pallas" and self.ell is not None:
            return Operator.matmat(self, V)    # vmap over the ELL kernel
        return self._bcoo() @ V

    def rmatmat(self, Q):
        if self.backend == "pallas" and self.ell is not None:
            return Operator.rmatmat(self, Q)
        return self.T._bcoo() @ Q

    def to_dense(self):
        return self._bcoo().todense()

    @property
    def T(self):
        ell = None if self.ell is None else \
            (self.ell[2], self.ell[3], self.ell[0], self.ell[1])
        return SparseOp(self.data, self.indices[:, ::-1],
                        (self.spshape[1], self.spshape[0]),
                        ell=ell, backend=self.backend)


@register_operator
@dataclasses.dataclass(frozen=True, eq=False)
class KroneckerOp(Operator):
    """``a ⊗ b`` — shape (m_a m_b, n_a n_b), never materialized.

    Matvecs use the reshape identity ``(A ⊗ B) vec(X) = vec(A X Bᵀ)`` (vec
    row-major, matching ``jnp.kron`` index order ``[i·m_b + k, j·n_b + l]``),
    so cost is two small GEMMs instead of one huge GEMV.  Factors are
    operators themselves — ``KroneckerOp(SparseOp(...), DenseOp(...))``
    composes.
    """

    a: Operator
    b: Operator

    _data_fields = ("a", "b")
    _meta_fields = ()

    @property
    def shape(self) -> tuple[int, int]:
        (ma, na), (mb, nb) = self.a.shape, self.b.shape
        return (ma * mb, na * nb)

    @property
    def dtype(self):
        return jnp.result_type(self.a.dtype, self.b.dtype)

    def mv(self, x):
        (ma, na), (mb, nb) = self.a.shape, self.b.shape
        X = x.reshape(na, nb)
        AX = self.a.matmat(X)                # (ma, nb)
        Y = self.b.matmat(AX.T).T            # (ma, mb): rows i are B @ AX[i]
        return Y.reshape(ma * mb)

    def rmv(self, y):
        (ma, na), (mb, nb) = self.a.shape, self.b.shape
        Y = y.reshape(ma, mb)
        AY = self.a.rmatmat(Y)               # (na, mb)
        X = self.b.rmatmat(AY.T).T           # (na, nb)
        return X.reshape(na * nb)

    def to_dense(self):
        return jnp.kron(self.a.to_dense(), self.b.to_dense())

    @property
    def T(self):
        return KroneckerOp(self.a.T, self.b.T)


@register_operator
@dataclasses.dataclass(frozen=True, eq=False)
class SinglePassOp(Operator):
    """Marks an operand as affordable to sweep only ONCE (streamed from
    disk / network, or simply too large to touch twice) — pure forwarding
    otherwise.  ``resolve_method`` sees ``single_pass_only`` and routes to
    the ``gnystrom`` solver, whose whole contract is one ``sketch_pass``.
    """

    inner: Operator

    _data_fields = ("inner",)
    _meta_fields = ()

    single_pass_only = True

    @property
    def shape(self) -> tuple[int, int]:
        return self.inner.shape

    @property
    def dtype(self):
        return self.inner.dtype

    def mv(self, p):
        return self.inner.mv(p)

    def rmv(self, q):
        return self.inner.rmv(q)

    def matmat(self, V):
        return self.inner.matmat(V)

    def rmatmat(self, Q):
        return self.inner.rmatmat(Q)

    def sketch_pass(self, omega, psi):
        return self.inner.sketch_pass(omega, psi)

    def to_dense(self):
        return self.inner.to_dense()

    @property
    def T(self):
        return SinglePassOp(self.inner.T)


_GRAM_SIDES = ("ata", "aat")


@register_operator
@dataclasses.dataclass(frozen=True, eq=False)
class GramOp(Operator):
    """``AᵀA`` (side="ata", n×n) or ``AAᵀ`` (side="aat", m×m) of ``inner``,
    applied as two matvecs — the Gram matrix itself is never formed.

    Symmetric by construction (``T`` is ``self``); its eigenvalues are
    ``σ(A)²``, which is what rank estimation on the normal equations needs.
    """

    inner: Operator
    side: str = "ata"

    _data_fields = ("inner",)
    _meta_fields = ("side",)

    @property
    def shape(self) -> tuple[int, int]:
        if self.side not in _GRAM_SIDES:
            raise ValueError(
                f"side must be one of {_GRAM_SIDES}, got {self.side!r}")
        d = self.inner.shape[1] if self.side == "ata" else self.inner.shape[0]
        return (d, d)

    @property
    def dtype(self):
        return self.inner.dtype

    def mv(self, p):
        if self.side == "ata":
            return self.inner.rmv(self.inner.mv(p))
        return self.inner.mv(self.inner.rmv(p))

    rmv = mv

    def matmat(self, V):
        if self.side == "ata":
            return self.inner.rmatmat(self.inner.matmat(V))
        return self.inner.matmat(self.inner.rmatmat(V))

    rmatmat = matmat

    @property
    def T(self):
        return self


def as_operator(A, *, backend: str = "xla"):
    """Coerce to the operator protocol.

    Operators and legacy ``LinOp`` closures pass through (both satisfy the
    same duck protocol); BCOO sparse matrices wrap into a :class:`SparseOp`;
    raw arrays wrap into a :class:`DenseOp`.
    """
    if isinstance(A, Operator):
        return A
    if hasattr(A, "mv") and hasattr(A, "rmv"):   # LinOp & look-alikes
        return A
    from jax.experimental import sparse as jsparse
    if isinstance(A, jsparse.BCOO):
        return SparseOp.from_bcoo(A, backend=backend)
    if backend not in _BACKENDS:
        raise ValueError(f"backend must be one of {_BACKENDS}, got {backend!r}")
    return DenseOp(jnp.asarray(A), backend=backend)


def to_dense(op) -> Array:
    """Materialize any protocol object (tests / small operands only)."""
    if isinstance(op, Operator):
        return op.to_dense()
    return op.matmat(jnp.eye(op.n, dtype=op.dtype))


def sharding_mesh(op):
    """The mesh a (possibly wrapped) operator is sharded over, or None.

    Structural duck check — ``repro.distributed.ShardedOp`` exposes a
    ``sharding_mesh`` property; wrapper operators are walked through the
    ``_data_fields`` every Operator already declares, so any future
    wrapper participates without registering here.  Lives in core (not
    ``repro.distributed``) so solvers can pick distributed code paths
    without an import cycle.
    """
    from jax.sharding import Mesh
    mesh = getattr(op, "sharding_mesh", None)
    if isinstance(mesh, Mesh):
        return mesh
    if not isinstance(op, Operator):
        return None
    stack = [getattr(op, f, None) for f in op._data_fields]
    while stack:
        x = stack.pop()
        if isinstance(x, Operator):
            mesh = sharding_mesh(x)
            if mesh is not None:
                return mesh
        elif isinstance(x, (tuple, list)):
            stack.extend(x)
    return None
