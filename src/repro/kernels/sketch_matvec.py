"""Sparse-sign sketch application Pallas kernels.

Sketch-based solvers (``rbk`` / ``gnystrom``) compress an operand through a
tall random test matrix ``T`` of shape (N, d) with ζ nonzeros per column,
each ±1/√ζ (Clarkson–Woodruff / Tropp sparse-sign ensemble), held in the
static ELL pack ``idx``/``signs`` of shape (d, ζ): row i lists the ζ source
rows that sketch coordinate i reads, and their signed weights.

    tapply:  Y = Tᵀ X   (X (N, b) → (d, b)),  Y[i, :] = Σ_s signs[i, s] X[idx[i, s], :]
    rapply:  Y = X T    (X (b, N) → (b, d)),  Y[:, i] = Σ_s signs[i, s] X[:, idx[i, s]]

Mosaic has no row gather from a VMEM-resident block (its ``gather``
lowering is a within-tile permutation), and X is operand-sized anyway.  So
each grid step rebuilds one (bd, bk) block of Tᵀ from the pack — ζ compares
of ``idx`` against a broadcasted iota of source indices — and contracts it
with a streamed (bk, bb) tile of X on the MXU, accumulating into the
resident output tile over the contraction axis (innermost).  X is read
once per sketch-row block; T is never written to HBM.  ``rapply`` takes
the operand untransposed (``A Ω`` without materializing ``Aᵀ``).

Unlike the SparseOp ELL pack (value-dependent row widths, built host-side),
the sketch pack has *static* shape (d, ζ) for a given spec — it is built
in-trace from a PRNG key by ``repro.core.sketch`` and therefore survives
``jit`` / ``vmap`` whole.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

Array = jax.Array

# default tile: 256 sketch rows × 512 source rows × 512 RHS columns per grid
# step; ``ops.py`` pads every axis longer than its tile to a tile multiple
# (shorter axes run as one full-extent block).  ZETA is the default
# nonzeros-per-column of the ensemble.
BD, BK, BB = 256, 512, 512
ZETA = 8
HIGHEST = jax.lax.Precision.HIGHEST


def _tblock(s_ref, i_ref, kk, bk: int) -> Array:
    """(bd, bk) block of Tᵀ over source rows [kk·bk, (kk+1)·bk)."""
    bd = s_ref.shape[0]
    src = kk * bk + jax.lax.broadcasted_iota(jnp.int32, (bd, bk), 1)
    S = jnp.zeros((bd, bk), jnp.float32)
    for s in range(i_ref.shape[1]):                  # ζ: small and static
        S = S + jnp.where(i_ref[:, s:s + 1] == src,
                          s_ref[:, s:s + 1].astype(jnp.float32), 0.0)
    return S


def _tapply_kernel(s_ref, i_ref, x_ref, o_ref):
    """Grid (d/bd, b/bb, N/bk): o (bd, bb) += Tᵀ-block @ X (bk, bb)."""
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    S = _tblock(s_ref, i_ref, kk, x_ref.shape[0])
    o_ref[...] += jnp.dot(S, x_ref[...].astype(jnp.float32),
                          precision=HIGHEST,
                          preferred_element_type=jnp.float32)


def _rapply_kernel(s_ref, i_ref, x_ref, o_ref):
    """Grid (d/bd, b/bb, N/bk): o (bb, bd) += X (bb, bk) @ T-block."""
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    S = _tblock(s_ref, i_ref, kk, x_ref.shape[1])
    o_ref[...] += jax.lax.dot_general(
        x_ref[...].astype(jnp.float32), S,
        dimension_numbers=(((1,), (1,)), ((), ())),
        precision=HIGHEST, preferred_element_type=jnp.float32)


def sketch_matmat(signs: Array, idx: Array, X: Array, *, bd: int = BD,
                  bk: int = BK, bb: int = BB, right: bool = False,
                  interpret: bool = True) -> Array:
    """``Tᵀ X`` (X (N, b) → (d, b)), or ``X T`` (X (b, N) → (b, d)) when
    ``right``.  signs/idx: (d, ζ).  d, N, b must be multiples of bd, bk,
    bb (``ops.py`` pads: zero-sign pack rows and zero X rows/columns
    contribute exactly 0)."""
    d, L = signs.shape
    b, N = X.shape if right else X.shape[::-1]
    assert d % bd == 0 and N % bk == 0 and b % bb == 0, \
        (signs.shape, X.shape, bd, bk, bb)
    pack = pl.BlockSpec((bd, L), lambda i, j, kk: (i, 0))
    if right:
        kernel = _rapply_kernel
        x_spec = pl.BlockSpec((bb, bk), lambda i, j, kk: (j, kk))
        out_spec = pl.BlockSpec((bb, bd), lambda i, j, kk: (j, i))
        out_shape = (b, d)
    else:
        kernel = _tapply_kernel
        x_spec = pl.BlockSpec((bk, bb), lambda i, j, kk: (kk, j))
        out_spec = pl.BlockSpec((bd, bb), lambda i, j, kk: (i, j))
        out_shape = (d, b)
    return pl.pallas_call(
        kernel,
        grid=(d // bd, b // bb, N // bk),
        in_specs=[pack, pack, x_spec],
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct(out_shape, jnp.float32),
        interpret=interpret,
        name="sketch_rapply" if right else "sketch_tapply",
    )(signs, idx, X)
