"""Reorthogonalization Pallas kernels (paper Alg 1 lines 6 / 13).

CGS against the basis ``Q (m, k)`` is two tall-skinny products:

    c = Qᵀ v          (k coefficients)
    w = v − Q c       (projection applied)

Each is one streaming pass over Q in ``(bm, k)`` row tiles (k ≤ a few
hundred, so a whole basis *row-block* fits VMEM; the k axis is never tiled).
The coefficient vector c lives in VMEM for the whole second pass.  Compared
to the naive jnp composition, nothing here materializes a (m, k)-shaped
temporary and Q is read exactly twice per CGS pass — the theoretical minimum
for classical Gram-Schmidt (the two products have a true dependency).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

Array = jax.Array

BM = 512           # Q row-block; (512, k<=1024) f32 ≤ 2 MiB of VMEM


def _qtv_kernel(q_ref, v_ref, o_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += jax.lax.dot_general(
        q_ref[...].astype(jnp.float32), v_ref[...].astype(jnp.float32),
        dimension_numbers=(((0,), (0,)), ((), ())),   # Qᵀ v
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)


def _sub_kernel(v_ref, q_ref, c_ref, o_ref):
    o_ref[...] = (v_ref[...].astype(jnp.float32)
                  - jnp.dot(q_ref[...].astype(jnp.float32),
                            c_ref[...].astype(jnp.float32),
                            precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32))


def qtv(Q: Array, v: Array, *, bm: int = BM, interpret: bool = True) -> Array:
    """c = Qᵀ v.  Q: (m, k); v: (m, 1) → (k, 1) f32."""
    m, k = Q.shape
    assert m % bm == 0, (m, bm)
    return pl.pallas_call(
        _qtv_kernel,
        grid=(m // bm,),
        in_specs=[
            pl.BlockSpec((bm, k), lambda i: (i, 0)),
            pl.BlockSpec((bm, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((k, 1), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((k, 1), jnp.float32),
        interpret=interpret,
        name="reorth_qtv",
    )(Q, v)


def subtract_qc(v: Array, Q: Array, c: Array, *, bm: int = BM,
                interpret: bool = True) -> Array:
    """w = v − Q c.  v: (m, 1); Q: (m, k); c: (k, 1) → (m, 1) f32."""
    m, k = Q.shape
    assert m % bm == 0, (m, bm)
    return pl.pallas_call(
        _sub_kernel,
        grid=(m // bm,),
        in_specs=[
            pl.BlockSpec((bm, 1), lambda i: (i, 0)),
            pl.BlockSpec((bm, k), lambda i: (i, 0)),
            pl.BlockSpec((k, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, 1), jnp.float32),
        interpret=interpret,
        name="reorth_subtract_qc",
    )(v, Q, c)
