"""Fused Lanczos matvec Pallas kernels: ``u = A p − α q`` and ``v = Aᵀ q − β p``.

TPU adaptation of the GK half-iteration (paper Alg 1 lines 5 / 12).  The
operation is HBM-bandwidth-bound (arithmetic intensity ≈ 1 FLOP/byte of A),
so the kernel's job is: stream A through VMEM exactly once, in MXU-aligned
``(bm, bn)`` tiles, accumulate in f32, and *fuse* the three-term-recurrence
subtraction so the result vector is written once (no separate axpy pass over
HBM).

Vectors are carried as ``(len, 1)`` columns — TPU Pallas wants ≥2-D refs and
the lane dimension maps onto the 128-wide VPU.

Grid convention: ``(m/bm, n/bn)`` with the contraction axis *innermost* so a
single output tile stays resident in VMEM across its accumulation steps
(sequential TPU grid).  For ``Aᵀ q`` the grid is ``(n/bn, m/bm)`` and each
A tile is transposed *inside* VMEM (free on the MXU via dimension numbers) —
A keeps one layout in HBM for both directions, which is what lets the GK
loop stream the same matrix forward and backward without a stored transpose.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

Array = jax.Array

# default tiles: (256, 512) f32 = 512 KiB of A per step — comfortably inside
# a ~16 MiB VMEM alongside the vector tiles and accumulator.
BM, BN = 256, 512


def _mv_kernel(a_ref, p_ref, y_ref, alpha_ref, o_ref):
    """One (i, j) step of u = A p − α y; j is the contraction index."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = -alpha_ref[0, 0] * y_ref[...].astype(jnp.float32)

    o_ref[...] += jnp.dot(a_ref[...].astype(jnp.float32),
                          p_ref[...].astype(jnp.float32),
                          precision=jax.lax.Precision.HIGHEST,
                          preferred_element_type=jnp.float32)


def _rmv_kernel(a_ref, q_ref, y_ref, beta_ref, o_ref):
    """One (i, j) step of v = Aᵀ q − β y; grid is (n/bn, m/bm)."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = -beta_ref[0, 0] * y_ref[...].astype(jnp.float32)

    o_ref[...] += jax.lax.dot_general(
        a_ref[...].astype(jnp.float32), q_ref[...].astype(jnp.float32),
        dimension_numbers=(((0,), (0,)), ((), ())),   # contract A rows: Aᵀ q
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)


def matvec_fused(A: Array, p: Array, y: Array, alpha: Array, *,
                 bm: int = BM, bn: int = BN, interpret: bool = True) -> Array:
    """u = A @ p − alpha * y.  A: (m, n); p: (n, 1); y: (m, 1) — f32 out.

    m, n must be multiples of (bm, bn); ``ops.py`` pads.
    """
    m, n = A.shape
    assert m % bm == 0 and n % bn == 0, (A.shape, bm, bn)
    alpha = jnp.asarray(alpha, jnp.float32).reshape(1, 1)
    return pl.pallas_call(
        _mv_kernel,
        grid=(m // bm, n // bn),
        in_specs=[
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            pl.BlockSpec((bn, 1), lambda i, j: (j, 0)),
            pl.BlockSpec((bm, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, 1), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, 1), jnp.float32),
        interpret=interpret,
        name="gk_matvec_fused",
    )(A, p, y, alpha)


def rmatvec_fused(A: Array, q: Array, y: Array, beta: Array, *,
                  bm: int = BM, bn: int = BN, interpret: bool = True) -> Array:
    """v = Aᵀ @ q − beta * y.  A: (m, n); q: (m, 1); y: (n, 1) — f32 out."""
    m, n = A.shape
    assert m % bm == 0 and n % bn == 0, (A.shape, bm, bn)
    beta = jnp.asarray(beta, jnp.float32).reshape(1, 1)
    return pl.pallas_call(
        _rmv_kernel,
        grid=(n // bn, m // bm),
        in_specs=[
            pl.BlockSpec((bm, bn), lambda i, j: (j, i)),
            pl.BlockSpec((bm, 1), lambda i, j: (j, 0)),
            pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, 1), jnp.float32),
        interpret=interpret,
        name="gk_rmatvec_fused",
    )(A, q, y, beta)
