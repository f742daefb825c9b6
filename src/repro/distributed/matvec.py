"""Pod-sharded GK matvecs: the paper's "huge matrix" regime on a real mesh.

The operator A (m, n) is sharded ``P(("pod","data"), "model")`` — rows over
the pod+data axes, columns over model (``repro.distributed.partition``
owns the layout).  The Lanczos vectors live sharded on the matching axis:

    q (m,)  P(("pod","data"))          p (n,)  P("model")

and the communication model is **one collective per GK half-step** in the
row-sharded layout (a "model" axis adds one matvec-reduce psum):

  * left half-step ``u = A p − α q`` — the local GEMV needs no reduction
    (rows are local); the CGS products are *stacked*: each shard computes
    the partial first coefficient ``c₁ = Qᵀu``, the partial basis Gram
    matrix ``G = QᵀQ`` and the partial ``‖u‖²``, and ONE psum carries all
    three.  Every further CGS pass is then local algebra —
    ``c_{i+1} = c_i − G c_i`` (exact: ``Qᵀ(w − Q c) = Qᵀw − G c``) — and
    the norm comes from the scalar identity
    ``‖u − Q d‖² = ‖u‖² − 2 dᵀc₁ + dᵀG d``.
  * right half-step ``v = Aᵀ q − β p`` — the transpose GEMV is partial
    over the row shards; ONE psum replicates it, after which CGS against
    the replicated P basis is entirely local.

So a 1e5 x 8e4 matrix (the paper's largest, NA for dense SVD) occupies
~60 MB per device on a 512-chip mesh and each half-iteration is one local
GEMV-plus-partial-``Qᵀu`` and a single rendezvous, instead of one
collective per dot (2·passes + 2 of them for CGS²).  With
``backend="pallas"`` the local shard work runs on the fused
``repro.kernels.gk_step`` tiles (matvec + first CGS product in one pass
over the shard, candidate VMEM-resident).

``ShardedOp`` is a pytree operator (``repro.core.operators``): the sharded
payload is the only leaf, the mesh rides as static aux data, so a whole
F-SVD solve over it jits as one program and plugs into ``repro.api``
unchanged.  The payload may be a dense matrix *or* the row-partitioned
ELL packs of a :class:`~repro.core.operators.SparseOp`
(:func:`sharded_operator` builds either; it also pushes sharding through
``GramOp`` / ``TransposedOp`` wrappers).  Operands whose shape does not
tile the mesh are zero-padded (exact for every reduction the solvers
issue) and report their logical shape.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.operators import (HIGHEST, GramOp, Operator, SparseOp,
                                  TransposedOp, cgs, hdot, register_operator)
from repro.distributed.partition import (operator_axes, operator_counts,
                                         operator_spec, padded_operand_shape,
                                         place_operator, shard_shape)
from repro.runtime.spans import span

__all__ = ["ShardedOp", "SparseShards", "place_operator", "sharded_operator",
           "operator_axes", "operator_spec", "shard_shape"]

Array = jax.Array


class SparseShards(NamedTuple):
    """Row-partitioned ELL packs of a sparse operand (one pack per shard).

    ``mv_vals``/``mv_cols`` are the forward ELL pack over the (padded)
    global rows — column ids are global, the right vector is replicated.
    ``rmv_vals``/``rmv_rows`` stack R per-shard transpose packs along dim 0
    (global shape (R·n, L')): each shard's block indexes **its own** local
    rows, so the transpose matvec is a pure gather over the local q block
    and one psum finishes ``Aᵀq`` — scatter never appears.
    """

    mv_vals: Array     # (m_pad, L)
    mv_cols: Array     # (m_pad, L) int32, global column ids
    rmv_vals: Array    # (R * n, L')
    rmv_rows: Array    # (R * n, L') int32, shard-local row ids


def _f32(x: Array) -> Array:
    return x.astype(jnp.float32)


def _acc_tdot(B: Array, x: Array) -> Array:
    """``Bᵀ x`` contracting rows with f32 accumulation; a narrower-storage
    basis (bf16) is never upcast in memory (same policy as ``cgs``)."""
    if B.dtype != x.dtype and B.dtype != jnp.float32:
        x = x.astype(B.dtype)
    return jax.lax.dot_general(
        B, x, dimension_numbers=(((0,), (0,)), ((), ())),
        precision=HIGHEST, preferred_element_type=jnp.float32)


def _acc_apply(B: Array, d: Array) -> Array:
    """``B d`` with f32 accumulation under the same storage policy."""
    if B.dtype != d.dtype and B.dtype != jnp.float32:
        d = d.astype(B.dtype)
    return jnp.dot(B, d, precision=HIGHEST, preferred_element_type=jnp.float32)


def _psum(x, axes):
    """``jax.lax.psum`` under the ``repro.op.psum`` scope, so each
    all-reduce names itself in a device trace."""
    with span("repro.op.psum"):
        return jax.lax.psum(x, axes)


def _gram_cgs_psum(w: Array, basis: Array, axes, passes: int,
                   c1_part: Optional[Array] = None) -> tuple[Array, Array]:
    """CGS^passes of the sharded column ``w`` against the equally-sharded
    ``basis`` with ONE stacked psum over ``axes``.

    Stacks the partial first coefficient ``c₁ = Qᵀw``, partial Gram matrix
    ``G = QᵀQ`` and partial ``‖w‖²`` into a single reduction; later passes
    use ``c_{i+1} = c_i − G c_i`` (exact, not an approximation) and the
    norm comes from ``‖w − Q d‖² = ‖w‖² − 2 dᵀc₁ + dᵀG d``.  Returns the
    sharded projected column and the replicated norm.
    """
    k = basis.shape[1]
    c1 = _acc_tdot(basis, w) if c1_part is None else c1_part   # (k, 1)
    G = _acc_tdot(basis, basis)                    # (k, k) partial
    ww = jnp.sum(_f32(w) * _f32(w)).reshape(1)     # (1,)  partial
    flat = jnp.concatenate([c1.ravel(), G.ravel(), ww])
    flat = _psum(flat, axes)
    c1 = flat[:k][:, None]
    G = flat[k:k + k * k].reshape(k, k)
    ww = flat[k + k * k]
    d = c1
    ci = c1
    for _ in range(passes - 1):
        ci = ci - hdot(G, ci)
        d = d + ci
    v = _f32(w) - _acc_apply(basis, d)
    nrm2 = (ww - 2.0 * jnp.sum(d * c1)
            + jnp.sum(d * hdot(G, d)))
    return v, jnp.sqrt(jnp.maximum(nrm2, 0.0))


def _local_cgs(w: Array, basis: Array, passes: int) -> tuple[Array, Array]:
    """Plain CGS^passes + direct norm on a fully replicated column."""
    v = cgs(_f32(w), basis, passes)
    return v, jnp.linalg.norm(v)


def _ell_mv(vals: Array, cols: Array, x: Array) -> Array:
    """``y = A x`` over a padded-ELL block: gather + lane reduction."""
    gathered = jnp.take(_f32(x)[:, 0], cols, axis=0)       # (rows, L)
    return jnp.sum(_f32(vals) * gathered, axis=1, keepdims=True)


def _ell_mm(vals: Array, cols: Array, X: Array) -> Array:
    """Block version: X (d, b) -> (rows, b)."""
    gathered = jnp.take(_f32(X), cols, axis=0)             # (rows, L, b)
    return jnp.einsum("rl,rlb->rb", _f32(vals), gathered)


def _local_mv(a, p_col: Array) -> Array:
    """Local shard of ``A p`` (partial over column shards, if any)."""
    if isinstance(a, SparseShards):
        return _ell_mv(a.mv_vals, a.mv_cols, p_col)
    return hdot(_f32(a), _f32(p_col))


def _local_rmv(a, q_col: Array) -> Array:
    """Local shard of ``Aᵀ q`` (partial over row shards)."""
    if isinstance(a, SparseShards):
        return _ell_mv(a.rmv_vals, a.rmv_rows, q_col)
    return jax.lax.dot_general(
        _f32(a), _f32(q_col), dimension_numbers=(((0,), (0,)), ((), ())),
        precision=HIGHEST, preferred_element_type=jnp.float32)


def _local_mm(a, X: Array) -> Array:
    if isinstance(a, SparseShards):
        return _ell_mm(a.mv_vals, a.mv_cols, X)
    return hdot(_f32(a), _f32(X))


def _local_rmm(a, X: Array) -> Array:
    if isinstance(a, SparseShards):
        return _ell_mm(a.rmv_vals, a.rmv_rows, X)
    return jax.lax.dot_general(
        _f32(a), _f32(X), dimension_numbers=(((0,), (0,)), ((), ())),
        precision=HIGHEST, preferred_element_type=jnp.float32)


def _a_specs(a_template, rows, col):
    """in_specs pytree for the operator payload."""
    if isinstance(a_template, SparseShards):
        blk = P(rows or None, None)
        return SparseShards(blk, blk, blk, blk)
    return P(rows or None, col)


@functools.lru_cache(maxsize=None)
def _matvec_fns(mesh: Mesh, sparse: bool):
    """shard_map'd fused three-term matvecs + block matmats (cached)."""
    rows, col = operator_axes(mesh)
    a_tmpl = SparseShards(None, None, None, None) if sparse else None
    a_spec = _a_specs(a_tmpl, rows, col)
    q_spec, p_spec = P(rows or None, None), P(col, None)

    def _mv(a, p_col, y_col, alpha):
        u = _local_mv(a, p_col)
        if col is not None:
            u = _psum(u, col)
        return u - alpha * _f32(y_col)

    def _rmv(a, q_col, y_col, beta):
        v = _local_rmv(a, q_col)
        if rows:
            v = _psum(v, rows)
        return v - beta * _f32(y_col)

    def _mm(a, X):
        Y = _local_mm(a, X)
        if col is not None:
            Y = _psum(Y, col)
        return Y

    def _rmm(a, X):
        Z = _local_rmm(a, X)
        if rows:
            Z = _psum(Z, rows)
        return Z

    def _sketch(a, om, ps):
        # one sweep over the local shard captures both directions: the
        # row-sharded range panel Y = A Ω needs no reduction (rows are
        # local), the co-range panel Z = Aᵀ Ψ is partial over the row
        # shards — each shard applies its own row block of Ψ and ONE psum
        # finishes it (a "model" axis adds the usual matvec-reduce psum).
        Y = _local_mm(a, om)
        if col is not None:
            Y = _psum(Y, col)
        Z = _local_rmm(a, ps)
        if rows:
            Z = _psum(Z, rows)
        return Y, Z

    sm = functools.partial(jax.shard_map, mesh=mesh, check_vma=False)
    return {
        "mv": sm(_mv, in_specs=(a_spec, p_spec, q_spec, P()),
                 out_specs=q_spec),
        "rmv": sm(_rmv, in_specs=(a_spec, q_spec, p_spec, P()),
                  out_specs=p_spec),
        "mm": sm(_mm, in_specs=(a_spec, P(col, None)),
                 out_specs=P(rows or None, None)),
        "rmm": sm(_rmm, in_specs=(a_spec, P(rows or None, None)),
                  out_specs=P(col, None)),
        "sketch": sm(_sketch,
                     in_specs=(a_spec, P(col, None), P(rows or None, None)),
                     out_specs=(P(rows or None, None), P(col, None))),
    }


@functools.lru_cache(maxsize=None)
def _step_fns(mesh: Mesh, passes: int, sparse: bool, pallas: bool):
    """shard_map'd fused Lanczos half-steps (cached per config).

    Row-sharded layout: exactly one psum per half-step.  A "model" axis
    adds the matvec-reduce psum (two total) and disables the Pallas local
    tiles (their fused ``Qᵀu`` would see a partial u).
    """
    rows, col = operator_axes(mesh)
    nrow, _ = operator_counts(mesh)
    a_tmpl = SparseShards(None, None, None, None) if sparse else None
    a_spec = _a_specs(a_tmpl, rows, col)
    q_spec, p_spec = P(rows or None, None), P(col, None)
    use_pallas = pallas and not sparse and col is None

    def _left(a, p_col, y_col, alpha, basis):
        # u = A p − α y, CGS^passes against the row-sharded basis, norm.
        if use_pallas and rows:
            from repro.kernels import ops as kops
            u, c1 = kops.local_mv_qtv(a, p_col, y_col, alpha, basis)
            return _gram_cgs_psum(u, basis, rows, passes, c1_part=c1)
        u = _local_mv(a, p_col)
        if col is not None:
            u = _psum(u, col)
        u = u - alpha * _f32(y_col)
        if rows:
            return _gram_cgs_psum(u, basis, rows, passes)
        return _local_cgs(u, basis, passes)

    def _right(a, q_col, y_col, beta, basis):
        # v = Aᵀ q − β y, CGS^passes against the (col-sharded) basis, norm.
        if use_pallas and rows:
            from repro.kernels import ops as kops
            v, c1 = kops.local_rmv_qtv(a, q_col, _f32(y_col) / nrow, beta,
                                       basis)
            nloc = v.shape[0]
            flat = _psum(
                jnp.concatenate([v.ravel(), c1.ravel()]), rows)
            v = flat[:nloc][:, None]
            c1 = flat[nloc:][:, None]
            v = v - _acc_apply(basis, c1)
            for _ in range(passes - 1):
                v = v - _acc_apply(basis, _acc_tdot(basis, v))
            return v, jnp.linalg.norm(v)
        v = _local_rmv(a, q_col)
        if rows:
            v = _psum(v, rows)
        v = v - beta * _f32(y_col)
        if col is not None:
            return _gram_cgs_psum(v, basis, col, passes)
        return _local_cgs(v, basis, passes)

    sm = functools.partial(jax.shard_map, mesh=mesh, check_vma=False)
    left = sm(_left, in_specs=(a_spec, p_spec, q_spec, P(),
                               P(rows or None, None)),
              out_specs=(q_spec, P()))
    right = sm(_right, in_specs=(a_spec, q_spec, p_spec, P(),
                                 P(col, None)),
               out_specs=(p_spec, P()))
    return left, right


def _pad_rows(x: Array, rows: int) -> Array:
    if x.shape[0] == rows:
        return x
    widths = [(0, rows - x.shape[0])] + [(0, 0)] * (x.ndim - 1)
    return jnp.pad(x, widths)


@register_operator
@dataclasses.dataclass(frozen=True, eq=False)
class ShardedOp(Operator):
    """Pod-sharded operator: matvecs are local shard work + one psum.

    ``A`` is the sharded payload — a dense matrix laid out by
    :func:`place_operator`, or :class:`SparseShards` ELL packs — and is
    the only pytree leaf; the mesh (plus the logical shape, when the
    payload is padded) is static aux data, so the operator crosses
    ``jit`` boundaries whole and the GK / F-SVD cores (and
    ``repro.api.factorize``) run on it unmodified.  Build with
    :func:`sharded_operator`, which handles padding, sparse packing and
    ``GramOp`` / ``TransposedOp`` wrappers.

    ``backend="pallas"`` runs the local shard of each fused Lanczos
    half-step on the ``repro.kernels.gk_step`` tiles (row-sharded dense
    payloads only).
    """

    A: Any
    mesh: Mesh
    lshape: Optional[Tuple[int, int]] = None
    backend: str = "xla"

    _data_fields = ("A",)
    _meta_fields = ("mesh", "lshape", "backend")

    # --- shape bookkeeping -------------------------------------------
    @property
    def shape(self) -> tuple[int, int]:
        if self.lshape is not None:
            return tuple(self.lshape)
        if isinstance(self.A, SparseShards):
            raise ValueError("sparse ShardedOp requires an explicit lshape "
                             "(build via sharded_operator)")
        return tuple(self.A.shape)

    @property
    def dtype(self):
        if isinstance(self.A, SparseShards):
            return self.A.mv_vals.dtype
        return self.A.dtype

    @property
    def _padded_shape(self) -> tuple[int, int]:
        return padded_operand_shape(self.shape, self.mesh)

    @property
    def _is_sparse(self) -> bool:
        return isinstance(self.A, SparseShards)

    def _payload(self):
        """Payload padded to the mesh tiling (no-op for factory-built ops;
        direct constructions of non-divisible dense operands pad here)."""
        if self._is_sparse:
            return self.A
        mp, np_ = self._padded_shape
        if tuple(self.A.shape) == (mp, np_):
            return self.A
        return jnp.pad(self.A, ((0, mp - self.A.shape[0]),
                                (0, np_ - self.A.shape[1])))

    # --- matvec protocol ---------------------------------------------
    def _fns(self):
        return _matvec_fns(self.mesh, self._is_sparse)

    def mv(self, p):
        m, _ = self.shape
        mp, np_ = self._padded_shape
        out = self._fns()["mv"](
            self._payload(), _pad_rows(_f32(p)[:, None], np_),
            jnp.zeros((mp, 1), jnp.float32), jnp.zeros((), jnp.float32))
        return out[:m, 0]

    def rmv(self, q):
        _, n = self.shape
        mp, np_ = self._padded_shape
        out = self._fns()["rmv"](
            self._payload(), _pad_rows(_f32(q)[:, None], mp),
            jnp.zeros((np_, 1), jnp.float32), jnp.zeros((), jnp.float32))
        return out[:n, 0]

    def mv_fused(self, p, y, alpha):
        m, _ = self.shape
        mp, np_ = self._padded_shape
        out = self._fns()["mv"](
            self._payload(), _pad_rows(_f32(p)[:, None], np_),
            _pad_rows(_f32(y)[:, None], mp),
            jnp.asarray(alpha, jnp.float32))
        return out[:m, 0]

    def rmv_fused(self, q, y, beta):
        _, n = self.shape
        mp, np_ = self._padded_shape
        out = self._fns()["rmv"](
            self._payload(), _pad_rows(_f32(q)[:, None], mp),
            _pad_rows(_f32(y)[:, None], np_),
            jnp.asarray(beta, jnp.float32))
        return out[:n, 0]

    def matmat(self, V):
        m, _ = self.shape
        _, np_ = self._padded_shape
        return self._fns()["mm"](self._payload(),
                                 _pad_rows(jnp.asarray(V), np_))[:m]

    def rmatmat(self, Q):
        _, n = self.shape
        mp, _ = self._padded_shape
        return self._fns()["rmm"](self._payload(),
                                  _pad_rows(jnp.asarray(Q), mp))[:n]

    def sketch_pass(self, omega, psi):
        """Both sketch directions in one shard_map body: per-shard panel
        GEMMs + a single psum on a row-sharded mesh (zero-padding the
        panels to the mesh tiling is exact — padded operand rows/cols are
        zero)."""
        m, n = self.shape
        mp, np_ = self._padded_shape
        Y, Z = self._fns()["sketch"](
            self._payload(),
            _pad_rows(jnp.asarray(omega.dense()), np_),
            _pad_rows(jnp.asarray(psi.dense()), mp))
        return Y[:m], Z[:n]

    # --- fused Lanczos half-steps (the scale-out seam) ---------------
    def lanczos_step(self, p, y, alpha, basis, *, passes: int = 2):
        m, _ = self.shape
        mp, np_ = self._padded_shape
        left, _ = _step_fns(self.mesh, passes, self._is_sparse,
                            self.backend == "pallas")
        u, nrm = left(self._payload(), _pad_rows(_f32(p)[:, None], np_),
                      _pad_rows(_f32(y)[:, None], mp),
                      jnp.asarray(alpha, jnp.float32),
                      _pad_rows(basis, mp))
        return u[:m, 0], nrm

    def lanczos_rstep(self, q, y, beta, basis, *, passes: int = 2):
        _, n = self.shape
        mp, np_ = self._padded_shape
        _, right = _step_fns(self.mesh, passes, self._is_sparse,
                             self.backend == "pallas")
        v, nrm = right(self._payload(), _pad_rows(_f32(q)[:, None], mp),
                       _pad_rows(_f32(y)[:, None], np_),
                       jnp.asarray(beta, jnp.float32),
                       _pad_rows(basis, np_))
        return v[:n, 0], nrm

    # --- placement helpers -------------------------------------------
    @property
    def sharding_mesh(self) -> Mesh:
        return self.mesh

    def place_basis(self, X: Array, side: str) -> Array:
        """Lay a basis buffer out on the operand's vector sharding, so
        host-loop solvers do not re-shard it on every eager step.

        Buffers whose leading dim does not tile the mesh stay as-is (the
        fused steps zero-pad them per call instead; ``device_put`` cannot
        shard unevenly)."""
        rows, col = operator_axes(self.mesh)
        nrow, ncol = operator_counts(self.mesh)
        parts = nrow if side == "left" else ncol
        if X.shape[0] % parts:
            return X
        spec = P(rows or None, None) if side == "left" else P(col, None)
        return jax.device_put(X, NamedSharding(self.mesh, spec))

    def to_dense(self):
        if self._is_sparse:
            return Operator.to_dense(self)
        m, n = self.shape
        return self.A[:m, :n]


def _sparse_shards(sp: SparseOp, mesh: Mesh) -> tuple[SparseShards, tuple]:
    """Build row-partitioned ELL packs for ``sp`` (host-side, concrete)."""
    import numpy as np

    from repro.kernels.sparse_matvec import ell_pack

    rows_n, cols_n = operator_counts(mesh)
    if cols_n > 1:
        raise NotImplementedError(
            "sparse ShardedOp supports row-sharded meshes only (no "
            "'model' axis); got mesh axes "
            f"{tuple(mesh.axis_names)}")
    from repro.core.padding import pad_dim
    m, n = sp.spshape
    m_pad = pad_dim(m, rows_n)
    m_loc = m_pad // rows_n
    data = np.asarray(sp.data)
    idx = np.asarray(sp.indices)

    vals, cols = (np.asarray(x) for x in ell_pack(data, idx, (m, n)))
    vals = np.pad(vals, ((0, m_pad - m), (0, 0)))
    cols = np.pad(cols, ((0, m_pad - m), (0, 0)))

    packs = []
    for j in range(rows_n):
        lo, hi = j * m_loc, (j + 1) * m_loc
        sel = (idx[:, 0] >= lo) & (idx[:, 0] < hi)
        loc = np.stack([idx[sel, 1], idx[sel, 0] - lo], axis=1)
        packs.append(tuple(np.asarray(x)
                           for x in ell_pack(data[sel], loc, (n, m_loc))))
    width = max(p[0].shape[1] for p in packs)
    rv = np.concatenate([np.pad(v, ((0, 0), (0, width - v.shape[1])))
                         for v, _ in packs])
    rr = np.concatenate([np.pad(r, ((0, 0), (0, width - r.shape[1])))
                         for _, r in packs])

    row_axes, _ = operator_axes(mesh)
    sh = NamedSharding(mesh, P(row_axes or None, None))
    shards = SparseShards(
        jax.device_put(jnp.asarray(vals), sh),
        jax.device_put(jnp.asarray(cols), sh),
        jax.device_put(jnp.asarray(rv), sh),
        jax.device_put(jnp.asarray(rr), sh))
    return shards, (m, n)


def sharded_operator(x, mesh: Mesh, backend: Optional[str] = None):
    """Lay any supported operand out on ``mesh`` as a sharded operator.

    Dense arrays (and ``DenseOp``) zero-pad to the mesh tiling and
    ``device_put`` under the pod-sharded layout; ``SparseOp`` builds
    row-partitioned ELL packs per shard; ``GramOp`` / ``TransposedOp``
    push the sharding onto their inner operand (so ``estimate_rank``'s
    matrix-free unwrapping and the fused Lanczos seams keep composing);
    an existing :class:`ShardedOp` passes through.
    """
    from jax.experimental import sparse as jsparse

    from repro.core.linop import LinOp
    if isinstance(x, ShardedOp):
        return x
    if isinstance(x, jsparse.BCOO):
        return sharded_operator(SparseOp.from_bcoo(x), mesh, backend)
    if isinstance(x, GramOp):
        return GramOp(sharded_operator(x.inner, mesh, backend), side=x.side)
    if isinstance(x, TransposedOp):
        return TransposedOp(sharded_operator(x.inner, mesh, backend))
    if isinstance(x, SparseOp):
        shards, lshape = _sparse_shards(x, mesh)
        return ShardedOp(shards, mesh, lshape=lshape,
                         backend=backend or x.backend)
    if isinstance(x, Operator) or isinstance(x, LinOp):
        from repro.core.operators import DenseOp
        if isinstance(x, DenseOp):
            return sharded_operator(x.A, mesh, backend or x.backend)
        raise TypeError(
            f"sharded_operator cannot lay out {type(x).__name__}; supported "
            "operands: dense arrays / DenseOp, SparseOp (row-sharded), "
            "GramOp / TransposedOp wrappers, ShardedOp")
    from repro.core.padding import pad_to
    A = jnp.asarray(x) if not isinstance(x, jax.Array) else x
    lshape = tuple(A.shape)
    A = pad_to(A, padded_operand_shape(lshape, mesh))
    return ShardedOp(place_operator(A, mesh), mesh, lshape=lshape,
                     backend=backend or "xla")
