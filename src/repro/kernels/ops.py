"""Public jit'd wrappers for the Pallas kernels.

Handles: (i) shape padding to tile multiples (zero rows/cols are exact for
every kernel here), (ii) vector ⇄ column reshaping, (iii) the
interpret-mode switch — compiled Mosaic on TPU, the Pallas interpreter on
the CPU backend (the test suite runs there with ``JAX_PLATFORMS=cpu``),
and an error on any other backend.

Padding an operand that is not a tile multiple copies it inside every
call; operands that are multiples of the (2048, 512) ``gk_step`` tile
pass through untouched.

These wrappers expose the same signatures as ``repro.kernels.ref`` so the
GK/F-SVD core can swap implementations via the ``use_kernels`` flag.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import count_sketch as _cs
from repro.kernels import gk_matvec as _gk
from repro.kernels import gk_step as _gs
from repro.kernels import lowrank_update as _lr
from repro.kernels import reorth as _ro
from repro.kernels import sketch_matvec as _sk
from repro.kernels import sparse_matvec as _sp

Array = jax.Array


def _interpret() -> bool:
    """Kernels compile for TPU and are interpreted on CPU; any other
    backend is refused rather than run through the interpreter."""
    backend = jax.default_backend()
    if backend not in ("tpu", "cpu"):
        raise RuntimeError(
            f"Pallas kernels run compiled on TPU or interpreted on CPU; "
            f"the default backend is {backend!r}")
    return backend == "cpu"


def _block(dim: int, tile: int) -> int:
    """One full-extent block for axes up to ``tile``, else ``tile``."""
    return dim if dim <= tile else tile


def _pad_to(x: Array, mult: int, axis: int) -> Array:
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _col(v: Array) -> Array:
    return v.reshape(-1, 1)


@functools.partial(jax.jit, static_argnames=("bm", "bn"))
def matvec_fused(A: Array, p: Array, y: Array, alpha, *, bm: int = _gk.BM,
                 bn: int = _gk.BN) -> Array:
    """u = A @ p − alpha * y  (vectors 1-D in, 1-D f32 out)."""
    m, n = A.shape
    bm, bn = min(bm, m) or 1, min(bn, n) or 1
    Ap = _pad_to(_pad_to(A, bm, 0), bn, 1)
    pp = _pad_to(_col(p), bn, 0)
    yp = _pad_to(_col(y), bm, 0)
    out = _gk.matvec_fused(Ap, pp, yp, alpha, bm=bm, bn=bn,
                           interpret=_interpret())
    return out[:m, 0]


@functools.partial(jax.jit, static_argnames=("bm", "bn"))
def rmatvec_fused(A: Array, q: Array, y: Array, beta, *, bm: int = _gk.BM,
                  bn: int = _gk.BN) -> Array:
    """v = Aᵀ @ q − beta * y."""
    m, n = A.shape
    bm, bn = min(bm, m) or 1, min(bn, n) or 1
    Ap = _pad_to(_pad_to(A, bm, 0), bn, 1)
    qp = _pad_to(_col(q), bm, 0)
    yp = _pad_to(_col(y), bn, 0)
    out = _gk.rmatvec_fused(Ap, qp, yp, beta, bm=bm, bn=bn,
                            interpret=_interpret())
    return out[:n, 0]


def _gk_tiles(A: Array, k: int, bm, bn) -> tuple[int, int]:
    """``gk_step`` (bm, bn) for A against a k-column basis: the k-derived
    defaults (``gk_step.tiles``) unless overridden, capped at A's shape."""
    dm, dn = _gs.tiles(k)
    m, n = A.shape
    return min(bm or dm, m) or 1, min(bn or dn, n) or 1


@functools.partial(jax.jit, static_argnames=("passes", "bm", "bn"))
def gk_step_fused(A: Array, p: Array, y: Array, alpha, Q: Array,
                  passes: int = 2, *, bm: Optional[int] = None,
                  bn: Optional[int] = None) -> tuple[Array, Array]:
    """Fused left GK half-step: ``u = A p − α y`` reorthogonalized
    CGS^passes against Q, plus its norm — the candidate vector never
    round-trips to HBM between the matvec and the first CGS product, and
    Q is read ``passes + 1`` times (the theoretical minimum: each CGS
    pass's two products have a true dependency, but the second product of
    pass i fuses with the first of pass i+1).

    A: (m, n); p: (n,); y: (m,); Q: (m, k) → (u (m,) f32, ‖u‖ () f32).
    """
    m, n = A.shape
    bm, bn = _gk_tiles(A, Q.shape[1], bm, bn)
    Ap = _pad_to(_pad_to(A, bm, 0), bn, 1)
    Qp = _pad_to(Q, bm, 0)
    pp = _pad_to(_col(p), bn, 0)
    yp = _pad_to(_col(y), bm, 0)
    interp = _interpret()
    u, c = _gs.mv_qtv(Ap, pp, yp, alpha, Qp, bm=bm, bn=bn, interpret=interp)
    if passes == 0:
        return u[:m, 0], jnp.linalg.norm(u[:m, 0])
    for _ in range(passes - 1):
        u, c = _gs.proj_qtv(u, Qp, c, bm=bm, interpret=interp)
    v, nrm2 = _gs.proj_norm(u, Qp, c, bm=bm, interpret=interp)
    return v[:m, 0], jnp.sqrt(nrm2[0, 0])


@functools.partial(jax.jit, static_argnames=("passes", "bm", "bn"))
def gk_rstep_fused(A: Array, q: Array, y: Array, beta, P: Array,
                   passes: int = 2, *, bm: Optional[int] = None,
                   bn: Optional[int] = None) -> tuple[Array, Array]:
    """Fused right GK half-step: ``v = Aᵀ q − β y`` vs the P basis.

    A: (m, n); q: (m,); y: (n,); P: (n, k) → (v (n,) f32, ‖v‖ () f32).
    """
    m, n = A.shape
    bm, bn = _gk_tiles(A, P.shape[1], bm, bn)
    Ap = _pad_to(_pad_to(A, bm, 0), bn, 1)
    Pp = _pad_to(P, bn, 0)
    qp = _pad_to(_col(q), bm, 0)
    yp = _pad_to(_col(y), bn, 0)
    interp = _interpret()
    v, c = _gs.rmv_qtv(Ap, qp, yp, beta, Pp, bm=bm, bn=bn, interpret=interp)
    if passes == 0:
        return v[:n, 0], jnp.linalg.norm(v[:n, 0])
    for _ in range(passes - 1):
        v, c = _gs.proj_qtv(v, Pp, c, bm=bn, interpret=interp)
    w, nrm2 = _gs.proj_norm(v, Pp, c, bm=bn, interpret=interp)
    return w[:n, 0], jnp.sqrt(nrm2[0, 0])


def local_mv_qtv(A: Array, p: Array, y: Array, alpha, Q: Array, *,
                 bm: Optional[int] = None, bn: Optional[int] = None
                 ) -> tuple[Array, Array]:
    """One fused pass of the ``gk_step`` stage-1 tile over a LOCAL shard:
    ``u = A p − α y`` plus the partial first CGS product ``c = Qᵀu``.

    Unlike :func:`gk_step_fused` this stops after stage 1 — the caller
    (the sharded Lanczos step body) psums ``c`` across shards before the
    remaining CGS algebra.  Column-vector shapes in/out: A (m, n);
    p (n, 1); y (m, 1); Q (m, k) → (u (m, 1), c (k, 1)) f32.  Not jitted:
    it is traced inside a ``shard_map`` body.
    """
    m, n = A.shape
    bm, bn = _gk_tiles(A, Q.shape[1], bm, bn)
    Ap = _pad_to(_pad_to(A, bm, 0), bn, 1)
    Qp = _pad_to(Q, bm, 0)
    pp = _pad_to(p, bn, 0)
    yp = _pad_to(y, bm, 0)
    u, c = _gs.mv_qtv(Ap, pp, yp, alpha, Qp, bm=bm, bn=bn,
                      interpret=_interpret())
    return u[:m], c


def local_rmv_qtv(A: Array, q: Array, y: Array, beta, P: Array, *,
                  bm: Optional[int] = None, bn: Optional[int] = None
                  ) -> tuple[Array, Array]:
    """Reverse direction of :func:`local_mv_qtv` over a local shard:
    ``v = Aᵀ q − β y`` plus the partial ``c = Pᵀv``.  A (m, n); q (m, 1);
    y (n, 1); P (n, k) → (v (n, 1), c (k, 1)) f32."""
    m, n = A.shape
    bm, bn = _gk_tiles(A, P.shape[1], bm, bn)
    Ap = _pad_to(_pad_to(A, bm, 0), bn, 1)
    Pp = _pad_to(P, bn, 0)
    qp = _pad_to(q, bm, 0)
    yp = _pad_to(y, bn, 0)
    v, c = _gs.rmv_qtv(Ap, qp, yp, beta, Pp, bm=bm, bn=bn,
                       interpret=_interpret())
    return v[:n], c


@functools.partial(jax.jit, static_argnames=("passes", "bm"))
def reorth(v: Array, Q: Array, passes: int = 2, *, bm: int = _ro.BM) -> Array:
    """CGS^passes: v − Q(Qᵀv), repeated.  v: (m,), Q: (m, k) → (m,) f32."""
    m, k = Q.shape
    bm = min(bm, m) or 1
    Qp = _pad_to(Q, bm, 0)
    vp = _pad_to(_col(v), bm, 0)
    interp = _interpret()
    for _ in range(passes):
        c = _ro.qtv(Qp, vp, bm=bm, interpret=interp)
        vp = _ro.subtract_qc(vp, Qp, c, bm=bm, interpret=interp)
    return vp[:m, 0]


@functools.partial(jax.jit, static_argnames=("bm", "bn"))
def lowrank_matmul(U: Array, s: Array, Vt: Array, *, bm: int = _lr.BM,
                   bn: int = _lr.BN) -> Array:
    """W = U diag(s) Vᵀ → (m, n) f32."""
    m, r = U.shape
    n = Vt.shape[1]
    bm, bn = min(bm, m) or 1, min(bn, n) or 1
    Up = _pad_to(U, bm, 0)
    Vtp = _pad_to(Vt, bn, 1)
    out = _lr.lowrank_matmul(Up, s, Vtp, bm=bm, bn=bn, interpret=_interpret())
    return out[:m, :n]


@functools.partial(jax.jit, static_argnames=("bm", "bk"))
def sparse_matvec(vals: Array, cols: Array, x: Array, *, bm: int = _sp.BM,
                  bk: int = _sp.BK) -> Array:
    """y = A @ x, A in padded-ELL rows (``sparse_matvec.ell_pack``) → (m,) f32.

    Pads rows to a ``bm`` multiple with empty (value 0, column 0) slots and
    x to a ``bk`` multiple with zeros; both paddings are exact.
    """
    m, _ = vals.shape
    bm, bk = _block(m, bm), _block(x.shape[0], bk)
    vp = _pad_to(vals, bm, 0)
    cp = _pad_to(cols, bm, 0)
    xp = _pad_to(x.reshape(1, -1), bk, 1)
    out = _sp.sparse_matvec(vp, cp, xp, bm=bm, bk=bk, interpret=_interpret())
    return out[:m, 0]


@functools.partial(jax.jit, static_argnames=("right", "bd", "bk", "bb"))
def sketch_matmat(signs: Array, idx: Array, X: Array, *, right: bool = False,
                  bd: int = _sk.BD, bk: int = _sk.BK,
                  bb: int = _sk.BB) -> Array:
    """``Tᵀ X`` (X (N, b) → (d, b) f32), or ``X T`` (X (b, N) → (b, d) f32)
    when ``right`` — T in the sparse-sign ELL pack (``core.sketch``).

    Pads sketch rows to a ``bd`` multiple (zero-sign slots reading row 0
    are exact) and X's source and RHS axes with zeros; the padding slices
    off after the call.
    """
    d, _ = signs.shape
    b, N = X.shape if right else X.shape[::-1]
    bd, bk, bb = _block(d, bd), _block(N, bk), _block(b, bb)
    sp = _pad_to(signs, bd, 0)
    ip = _pad_to(idx, bd, 0)
    src_ax, rhs_ax = (1, 0) if right else (0, 1)
    Xp = _pad_to(_pad_to(X, bk, src_ax), bb, rhs_ax)
    out = _sk.sketch_matmat(sp, ip, Xp, bd=bd, bk=bk, bb=bb, right=right,
                            interpret=_interpret())
    return out[:b, :d] if right else out[:d, :b]


@functools.partial(jax.jit, static_argnames=("shape", "be"))
def scatter_add(rows: Array, cols: Array, vals: Array,
                shape: tuple[int, int], *, be: int = _cs.BE) -> Array:
    """Dense (m, d) f32 accumulation of a COO entry stream; duplicate
    coordinates sum (count-sketch collision semantics).

    Pads the entry count to a ``be`` multiple with (row 0, col 0, val 0)
    entries — exactly zero contribution — and the output panel to f32
    tile multiples, sliced off after the call.
    """
    m, d = shape
    E = rows.shape[0]
    if E == 0:
        return jnp.zeros((m, d), jnp.float32)
    be = min(be, E)
    rp = _pad_to(rows.reshape(-1, 1).astype(jnp.int32), be, 0)[:, 0]
    cp = _pad_to(cols.reshape(-1, 1).astype(jnp.int32), be, 0)[:, 0]
    vp = _pad_to(vals.reshape(-1, 1), be, 0)[:, 0]
    mp = m + ((-m) % 8)
    dp = d + ((-d) % 128)
    out = _cs.scatter_add(rp, cp, vp, (mp, dp), be=be,
                          interpret=_interpret())
    return out[:m, :d]
