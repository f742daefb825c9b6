"""Low-rank materialization Pallas kernel: ``W = U diag(s) Vᵀ``.

Used when the RSGD retraction output (or a compressed gradient) must be
densified — e.g. applying a rank-r update to an optimizer's dense parameter
block.  Output-stationary tiling: each (bm, bn) tile of W is produced by one
(bm, r) × (r, bn) MXU contraction; r ≤ a few hundred so both factor slabs sit
in VMEM, and W is *written once, never read* (the jnp composition would
materialize U·diag(s) first — an extra (m, r) HBM round-trip).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

Array = jax.Array

BM, BN = 256, 256


def _lr_kernel(u_ref, s_ref, vt_ref, o_ref):
    us = u_ref[...].astype(jnp.float32) * s_ref[...].astype(jnp.float32)
    o_ref[...] = jnp.dot(us, vt_ref[...].astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST,
                         preferred_element_type=jnp.float32)


def lowrank_matmul(U: Array, s: Array, Vt: Array, *, bm: int = BM,
                   bn: int = BN, interpret: bool = True) -> Array:
    """W = U diag(s) Vᵀ.  U: (m, r); s: (r,); Vt: (r, n) → (m, n) f32."""
    m, r = U.shape
    r2, n = Vt.shape
    assert r == r2 and m % bm == 0 and n % bn == 0, (U.shape, Vt.shape, bm, bn)
    s2 = jnp.asarray(s, jnp.float32).reshape(1, r)
    return pl.pallas_call(
        _lr_kernel,
        grid=(m // bm, n // bn),
        in_specs=[
            pl.BlockSpec((bm, r), lambda i, j: (i, 0)),
            pl.BlockSpec((1, r), lambda i, j: (0, 0)),
            pl.BlockSpec((r, bn), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        interpret=interpret,
        name="lowrank_matmul",
    )(U, s2, Vt)

