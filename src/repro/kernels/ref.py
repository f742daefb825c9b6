"""Pure-jnp oracles for every Pallas kernel (the allclose reference)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

Array = jax.Array


def matvec_fused(A: Array, p: Array, y: Array, alpha) -> Array:
    """u = A @ p - alpha * y   (GK line 5 / 12, f32 accumulate)."""
    return (A.astype(jnp.float32) @ p.astype(jnp.float32)
            - jnp.asarray(alpha, jnp.float32) * y.astype(jnp.float32))


def rmatvec_fused(A: Array, q: Array, y: Array, beta) -> Array:
    """v = A^T @ q - beta * y."""
    return (A.astype(jnp.float32).T @ q.astype(jnp.float32)
            - jnp.asarray(beta, jnp.float32) * y.astype(jnp.float32))


def qtv(Q: Array, v: Array) -> Array:
    """c = Q^T v  (reorthogonalization coefficients)."""
    return Q.astype(jnp.float32).T @ v.astype(jnp.float32)


def subtract_qc(v: Array, Q: Array, c: Array) -> Array:
    """w = v - Q c  (apply the CGS projection)."""
    return v.astype(jnp.float32) - Q.astype(jnp.float32) @ c.astype(jnp.float32)


def reorth(v: Array, Q: Array, passes: int = 2) -> Array:
    for _ in range(passes):
        v = subtract_qc(v, Q, qtv(Q, v))
    return v


def gk_step(A: Array, p: Array, y: Array, alpha, Q: Array,
            passes: int = 2) -> tuple[Array, Array]:
    """Fused left GK half-step: u = A p − α y, CGS^passes vs Q, and ‖u‖."""
    u = reorth(matvec_fused(A, p, y, alpha), Q, passes)
    return u, jnp.linalg.norm(u)


def gk_rstep(A: Array, q: Array, y: Array, beta, P: Array,
             passes: int = 2) -> tuple[Array, Array]:
    """Fused right GK half-step: v = Aᵀ q − β y, CGS^passes vs P, and ‖v‖."""
    v = reorth(rmatvec_fused(A, q, y, beta), P, passes)
    return v, jnp.linalg.norm(v)


def lowrank_matmul(U: Array, s: Array, Vt: Array) -> Array:
    """W = U diag(s) V^T  (retraction materialization)."""
    return (U.astype(jnp.float32) * s.astype(jnp.float32)[None, :]) \
        @ Vt.astype(jnp.float32)


def sparse_matvec(vals: Array, cols: Array, x: Array) -> Array:
    """y = A @ x for A in padded-ELL rows (vals/cols (m, L), x (n,))."""
    return jnp.sum(vals.astype(jnp.float32)
                   * x.astype(jnp.float32)[cols], axis=1)


def sketch_matmat(signs: Array, idx: Array, X: Array) -> Array:
    """Y = Tᵀ @ X for T in the sparse-sign ELL pack (signs/idx (d, ζ),
    X (N, b)) — sketch row i sums its ζ signed source rows of X."""
    return jnp.einsum("ds,dsb->db", signs.astype(jnp.float32),
                      X.astype(jnp.float32)[idx],
                      precision=jax.lax.Precision.HIGHEST)


def scatter_add(rows: Array, cols: Array, vals: Array,
                shape: tuple[int, int]) -> Array:
    """Dense (m, d) f32 accumulation of a COO stream — the einsum oracle
    for the count-sketch scatter kernel.  Destinations are expanded to
    one-hot matrices and contracted over the entry axis, so duplicate
    coordinates *sum* (the semantics the kernel must match)."""
    m, d = shape
    R = (rows[:, None] == jnp.arange(m, dtype=rows.dtype)[None, :]
         ).astype(jnp.float32)
    H = (cols[:, None] == jnp.arange(d, dtype=cols.dtype)[None, :]
         ).astype(jnp.float32) * vals.astype(jnp.float32)[:, None]
    return jnp.einsum("em,ed->md", R, H)
