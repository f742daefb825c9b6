"""COO scatter-add Pallas kernel — the repo's first scatter kernel.

Every prior kernel here is gather-only by construction: the ELL packs of
``sparse_matvec``/``sketch_matvec`` pin each *destination* to a static row
so grid steps only ever read at data-dependent indices.  A streamed COO
delta breaks that trick — entry ``e`` lands at ``(rows[e], cols[e])``,
the destination itself is data, and duplicate coordinates must **sum**
(count-sketch semantics: hash collisions accumulate, they don't clobber).

The kernel therefore owns the whole accumulator panel across the grid and
lowers the scatter to an on-chip one-hot contraction: each grid step takes
a block of ``be`` entries, expands the destination coordinates against a
broadcasted iota into one-hot matrices ``R`` (be, m) and ``H`` (be, d),
folds the values into ``H``, and accumulates ``o += Rᵀ H`` — one MXU
matmul per block instead of ``be`` serialized dynamic-index writes, which
TPUs cannot vectorize.  Duplicates inside a block meet in the contraction
over the entry axis; duplicates across blocks meet in the ``+=`` on the
resident output (TPU grids are sequential, so the accumulation is sound).

This is the fold primitive of ``repro.sketchres``: a hashed count-sketch
update expands each operand entry into ζ signed slot entries and lands
them here.  Padding entries are (row 0, col 0, value 0) — exactly zero
contribution — so the ``ops.py`` wrapper's block-multiple padding is exact.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

Array = jax.Array

# default entries-per-grid-step; the one-hot expansions are (be, m) and
# (be, d), so be also sets the sublane extent of the MXU contraction.
BE = 128


def _scatter_kernel(r_ref, c_ref, v_ref, o_ref):
    """One entry block: o += Σ_e vals[e] · e_rows[e] e_cols[e]ᵀ.

    The output panel maps to the same block at every grid step (index map
    ``lambda i: (0, 0)``); step 0 zero-initializes it and every step
    accumulates, so the kernel is a reduction over entry blocks.
    """
    @pl.when(pl.program_id(0) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    rows = r_ref[...].reshape(-1)                       # (be,) int32
    cols = c_ref[...].reshape(-1)
    vals = v_ref[...].reshape(-1).astype(jnp.float32)
    be = rows.shape[0]
    m, d = o_ref.shape
    # destination one-hots: R[e, i] = [rows[e] == i], H[e, j] likewise with
    # the entry value folded in — duplicates sum in the e-contraction.
    R = (rows[:, None] == jax.lax.broadcasted_iota(
        jnp.int32, (be, m), 1)).astype(jnp.float32)
    H = (cols[:, None] == jax.lax.broadcasted_iota(
        jnp.int32, (be, d), 1)).astype(jnp.float32) * vals[:, None]
    o_ref[...] += jnp.dot(R.T, H, precision=jax.lax.Precision.HIGHEST,
                          preferred_element_type=jnp.float32)


def scatter_add(rows: Array, cols: Array, vals: Array,
                shape: tuple[int, int], *, be: int = BE,
                interpret: bool = True) -> Array:
    """Dense (m, d) f32 accumulation of a COO entry stream.

    rows/cols: (E,) int32 in [0, m) / [0, d); vals: (E,).  E must be a
    multiple of ``be`` (``ops.py`` pads with zero-value entries at (0, 0),
    which contribute exactly 0); duplicate coordinates sum.
    """
    E = rows.shape[0]
    assert E % be == 0, (E, be)
    m, d = shape
    return pl.pallas_call(
        _scatter_kernel,
        grid=(E // be,),
        in_specs=[
            pl.BlockSpec((be, 1), lambda i: (i, 0)),
            pl.BlockSpec((be, 1), lambda i: (i, 0)),
            pl.BlockSpec((be, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((m, d), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((m, d), jnp.float32),
        interpret=interpret,
    )(rows.reshape(E, 1), cols.reshape(E, 1), vals.reshape(E, 1))
