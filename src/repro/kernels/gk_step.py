"""Fused GK iteration-step Pallas kernels (paper Alg 1 lines 5-8 / 12-14).

One GK half-iteration is ``u = A p − α y`` followed by CGS2 against the
basis ``Q`` and a norm.  The unfused composition (``gk_matvec`` +
``reorth`` + a jnp norm) round-trips the candidate vector through HBM
between every stage and reads Q four times per CGS2 step (two ``Qᵀv``
products, two ``v − Qc`` projections).  These kernels pipeline the step so
the candidate never leaves VMEM between the matvec and the first CGS
product, and Q is read the theoretical minimum three times per CGS2 step:

  stage 1  ``mv_qtv``     streams A row-block-wise, accumulates the matvec
                          into the resident output tile and — on the last
                          contraction step, while the tile is still in
                          VMEM — accumulates the first CGS coefficient
                          product ``c₁ = Qᵀu``.          (reads A once, Q once)
  stage 2  ``proj_qtv``   one pass over Q: applies ``w = u − Q c₁`` and
                          accumulates ``c₂ = Qᵀw`` from the tile just
                          computed.                       (reads Q once)
  stage 3  ``proj_norm``  one pass over Q: applies ``v = w − Q c₂`` and
                          accumulates ``‖v‖²`` in the epilogue, so the
                          normalization scalar needs no extra pass.

CGS^p generalizes as stage1 → (p−1)× stage2 → stage3.  The reverse
half-iteration (``v = Aᵀ q − β y`` against the right basis P) shares
stages 2/3; only stage 1 differs (``rmv_qtv`` transposes A tiles in VMEM,
same trick as ``gk_matvec.rmatvec_fused``).

Mixed precision falls out for free: bases and A may be stored bf16 in HBM
(half the bytes of the bandwidth-bound streams); every tile is upcast in
VMEM and all dots/reductions accumulate f32 (``preferred_element_type``).

Vectors ride as ``(len, 1)`` columns; coefficient vectors as ``(k, 1)``
with a constant output index so they stay VMEM-resident across the whole
grid (same convention as ``reorth.qtv``).  ``ops.py`` pads shapes to tile
multiples — zero rows/cols are exact for every stage here.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

Array = jax.Array

# The fused pipeline is the only kernel in flight, so it takes a much
# taller row block than gk_matvec's (256, 512): fewer grid steps amortize
# per-step overhead and the basis row-block is reused across the whole
# contraction.  (2048, 512) is the widest tile; :func:`tiles` narrows the
# basis-side block as k grows, because the (block, k) basis tile and the
# (k, 1) coefficient columns (lane-padded to 128 in VMEM) are what fill
# VMEM.  The v5e compiler refuses (2048, 512) with a k = 512 basis in f32
# (more than the 128 MiB VMEM once double-buffered); every block below
# keeps the basis tile within ``_BASIS_ELEMS`` elements.
BM, BN = 2048, 512
_BASIS_ELEMS = 1 << 20          # elements of one (block, k) basis tile
_MIN_BM = 64
VMEM_LIMIT = 100 * 2**20        # scoped-VMEM request: below v5e's 128 MiB
HIGHEST = jax.lax.Precision.HIGHEST


def _pow2_floor(x: int) -> int:
    return 1 << max(int(x).bit_length() - 1, 0)


def tiles(k: int) -> tuple[int, int]:
    """Default ``(bm, bn)`` for a basis of ``k`` columns.

    Both stay powers of two (so any operand that is a multiple of the
    (2048, 512) tile needs no padding), shrink until one (block, k)
    basis tile holds at most ``_BASIS_ELEMS`` elements, and never drop
    below the sublane/lane minimums (``bm`` ≥ 64, ``bn`` ≥ 128 lanes).
    """
    kp = -(-max(k, 1) // 128) * 128          # lane-padded basis width
    fit = _pow2_floor(max(_BASIS_ELEMS // kp, 1))
    return max(min(BM, fit), _MIN_BM), max(min(BN, fit), 128)


def _params():
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT)


def _rows_dot(a: Array, b: Array) -> Array:
    """aᵀ b contracting the row (sublane) axis, f32 accumulate."""
    return jax.lax.dot_general(
        a.astype(jnp.float32), b.astype(jnp.float32),
        dimension_numbers=(((0,), (0,)), ((), ())),
        precision=HIGHEST, preferred_element_type=jnp.float32)


def _mv_qtv_kernel(a_ref, p_ref, y_ref, alpha_ref, q_ref, u_ref, c_ref):
    """Grid (m/bm, n/bn), contraction j innermost: u tile stays resident."""
    i, j = pl.program_id(0), pl.program_id(1)
    nj = pl.num_programs(1)

    @pl.when(j == 0)
    def _init_u():
        u_ref[...] = -alpha_ref[0, 0] * y_ref[...].astype(jnp.float32)

    u_ref[...] += jnp.dot(a_ref[...].astype(jnp.float32),
                          p_ref[...].astype(jnp.float32),
                          precision=HIGHEST,
                          preferred_element_type=jnp.float32)

    @pl.when(jnp.logical_and(i == 0, j == 0))
    def _init_c():
        c_ref[...] = jnp.zeros_like(c_ref)

    # the finished u tile is still in VMEM — take its CGS contribution now
    @pl.when(j == nj - 1)
    def _acc_c():
        c_ref[...] += _rows_dot(q_ref[...], u_ref[...])


def _rmv_qtv_kernel(a_ref, q_ref, y_ref, beta_ref, pb_ref, v_ref, c_ref):
    """Reverse direction: grid (n/bn, m/bm); A tiles transpose in VMEM."""
    i, j = pl.program_id(0), pl.program_id(1)
    nj = pl.num_programs(1)

    @pl.when(j == 0)
    def _init_v():
        v_ref[...] = -beta_ref[0, 0] * y_ref[...].astype(jnp.float32)

    v_ref[...] += _rows_dot(a_ref[...], q_ref[...])        # Aᵀ q tile

    @pl.when(jnp.logical_and(i == 0, j == 0))
    def _init_c():
        c_ref[...] = jnp.zeros_like(c_ref)

    @pl.when(j == nj - 1)
    def _acc_c():
        c_ref[...] += _rows_dot(pb_ref[...], v_ref[...])


def _proj_qtv_kernel(u_ref, q_ref, cin_ref, w_ref, cout_ref):
    """w = u − Q c (applied) and c' = Qᵀ w (accumulated) in one Q pass."""
    i = pl.program_id(0)
    w = (u_ref[...].astype(jnp.float32)
         - jnp.dot(q_ref[...].astype(jnp.float32), cin_ref[...],
                   precision=HIGHEST, preferred_element_type=jnp.float32))
    w_ref[...] = w

    @pl.when(i == 0)
    def _init_c():
        cout_ref[...] = jnp.zeros_like(cout_ref)

    cout_ref[...] += _rows_dot(q_ref[...], w)


def _proj_norm_kernel(u_ref, q_ref, cin_ref, v_ref, nrm_ref):
    """v = u − Q c and the ‖v‖² epilogue in one Q pass."""
    i = pl.program_id(0)
    v = (u_ref[...].astype(jnp.float32)
         - jnp.dot(q_ref[...].astype(jnp.float32), cin_ref[...],
                   precision=HIGHEST, preferred_element_type=jnp.float32))
    v_ref[...] = v

    @pl.when(i == 0)
    def _init_n():
        nrm_ref[...] = jnp.zeros_like(nrm_ref)

    # a (1, 1) vector block: Mosaic cannot store a scalar to VMEM
    nrm_ref[...] += jnp.sum(v * v, axis=0, keepdims=True)


def mv_qtv(A: Array, p: Array, y: Array, alpha: Array, Q: Array, *,
           bm: int = BM, bn: int = BN,
           interpret: bool = True) -> tuple[Array, Array]:
    """(u, c) = (A p − α y, Qᵀ u) in one streaming pass over A and Q.

    A: (m, n); p: (n, 1); y: (m, 1); Q: (m, k) → u (m, 1), c (k, 1) f32.
    m, n must be tile multiples (``ops.py`` pads); k is never tiled.
    """
    m, n = A.shape
    k = Q.shape[1]
    assert m % bm == 0 and n % bn == 0, (A.shape, bm, bn)
    alpha = jnp.asarray(alpha, jnp.float32).reshape(1, 1)
    return pl.pallas_call(
        _mv_qtv_kernel,
        grid=(m // bm, n // bn),
        in_specs=[
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            pl.BlockSpec((bn, 1), lambda i, j: (j, 0)),
            pl.BlockSpec((bm, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
            pl.BlockSpec((bm, k), lambda i, j: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bm, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((k, 1), lambda i, j: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, 1), jnp.float32),
            jax.ShapeDtypeStruct((k, 1), jnp.float32),
        ],
        interpret=interpret,
        name="gk_step_mv_qtv",
        compiler_params=_params(),
    )(A, p, y, alpha, Q)


def rmv_qtv(A: Array, q: Array, y: Array, beta: Array, P: Array, *,
            bm: int = BM, bn: int = BN,
            interpret: bool = True) -> tuple[Array, Array]:
    """(v, c) = (Aᵀ q − β y, Pᵀ v).  A: (m, n); q: (m, 1); y, v: (n, 1);
    P: (n, k) → v (n, 1), c (k, 1) f32."""
    m, n = A.shape
    k = P.shape[1]
    assert m % bm == 0 and n % bn == 0, (A.shape, bm, bn)
    beta = jnp.asarray(beta, jnp.float32).reshape(1, 1)
    return pl.pallas_call(
        _rmv_qtv_kernel,
        grid=(n // bn, m // bm),
        in_specs=[
            pl.BlockSpec((bm, bn), lambda i, j: (j, i)),
            pl.BlockSpec((bm, 1), lambda i, j: (j, 0)),
            pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
            pl.BlockSpec((bn, k), lambda i, j: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((k, 1), lambda i, j: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
            jax.ShapeDtypeStruct((k, 1), jnp.float32),
        ],
        interpret=interpret,
        name="gk_step_rmv_qtv",
        compiler_params=_params(),
    )(A, q, y, beta, P)


def proj_qtv(u: Array, Q: Array, c: Array, *, bm: int = BM,
             interpret: bool = True) -> tuple[Array, Array]:
    """(w, c') = (u − Q c, Qᵀ w) in one pass over Q.
    u: (m, 1); Q: (m, k); c: (k, 1)."""
    m, k = Q.shape
    assert m % bm == 0, (m, bm)
    return pl.pallas_call(
        _proj_qtv_kernel,
        grid=(m // bm,),
        in_specs=[
            pl.BlockSpec((bm, 1), lambda i: (i, 0)),
            pl.BlockSpec((bm, k), lambda i: (i, 0)),
            pl.BlockSpec((k, 1), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bm, 1), lambda i: (i, 0)),
            pl.BlockSpec((k, 1), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, 1), jnp.float32),
            jax.ShapeDtypeStruct((k, 1), jnp.float32),
        ],
        interpret=interpret,
        name="gk_step_proj_qtv",
        compiler_params=_params(),
    )(u, Q, c)


def proj_norm(u: Array, Q: Array, c: Array, *, bm: int = BM,
              interpret: bool = True) -> tuple[Array, Array]:
    """(v, ‖v‖²) = (u − Q c, Σ v²) in one pass over Q.
    u: (m, 1); Q: (m, k); c: (k, 1) → v (m, 1), nrm2 (1, 1)."""
    m, k = Q.shape
    assert m % bm == 0, (m, bm)
    return pl.pallas_call(
        _proj_norm_kernel,
        grid=(m // bm,),
        in_specs=[
            pl.BlockSpec((bm, 1), lambda i: (i, 0)),
            pl.BlockSpec((bm, k), lambda i: (i, 0)),
            pl.BlockSpec((k, 1), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bm, 1), lambda i: (i, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, 1), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ],
        interpret=interpret,
        name="gk_step_proj_norm",
        compiler_params=_params(),
    )(u, Q, c)
