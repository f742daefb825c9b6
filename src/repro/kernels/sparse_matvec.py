"""Row-blocked ELL sparse matvec Pallas kernel: ``y = A x`` for sparse A.

The COO triplets are packed into padded ELL rows — ``vals``/``cols`` of
shape (m, L) with L = max row population, zero-padded (slot value 0 at
column 0 contributes exactly 0):

    y[i] = Σ_s vals[i, s] * x[cols[i, s]]

Mosaic has no gather from a VMEM-resident vector (its ``gather`` lowering
is a within-tile permutation), so each grid step owns ``bm`` rows and one
``bk``-wide block of x and rebuilds the matching (bm, bk) block of A from
the pack: L compares of ``cols`` against a broadcasted iota of column ids,
selecting ``vals``.  The product with the x block is a VPU multiply and a
lane reduction in f32, accumulated over the column blocks (innermost grid
axis).  The pack is read once per column block, x once per row block, and
the work is O(m·n·L) on the VPU: dense-operand cost, paid to run at all
on the chip.  The transpose direction reuses the same kernel on the ELL
pack of Aᵀ (built once, host-side) — scatter never appears.

The pack is value-dependent (L = max nnz per row), so ``ell_pack`` runs
host-side on concrete coordinates (NumPy) — done once at ``SparseOp``
construction, never under a trace.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

Array = jax.Array

# default tile: 256 rows × 512 columns of x per grid step; ``ops.py`` pads
# each axis longer than its tile to a tile multiple.
BM, BK = 256, 512


def ell_pack(data, indices, spshape) -> tuple[Array, Array]:
    """Pack COO triplets into padded ELL rows (host-side, concrete arrays).

    Returns ``(vals (m, L), cols (m, L))`` with L = max row population
    (min 1).  Empty slots carry (value 0, column 0) — exact, because
    ``0 * x[0] == 0``.  Duplicate coordinates keep separate slots (sum
    semantics, matching BCOO).
    """
    m, _ = spshape
    d = np.asarray(data)
    idx = np.asarray(indices)
    rows, cols = idx[:, 0].astype(np.int64), idx[:, 1].astype(np.int64)
    counts = np.bincount(rows, minlength=m)
    L = max(int(counts.max(initial=0)), 1)
    vals = np.zeros((m, L), d.dtype)
    colp = np.zeros((m, L), np.int32)
    order = np.argsort(rows, kind="stable")
    r_sorted = rows[order]
    offsets = np.concatenate([[0], np.cumsum(counts)])
    slot = np.arange(r_sorted.shape[0]) - offsets[r_sorted]
    vals[r_sorted, slot] = d[order]
    colp[r_sorted, slot] = cols[order]
    return jnp.asarray(vals), jnp.asarray(colp)


def _spmv_kernel(v_ref, c_ref, x_ref, o_ref):
    """Grid (m/bm, n/bk): o (bm, 1) += A-block (bm, bk) · x-block."""
    kk = pl.program_id(1)

    @pl.when(kk == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    bm, bk = v_ref.shape[0], x_ref.shape[1]
    col = kk * bk + jax.lax.broadcasted_iota(jnp.int32, (bm, bk), 1)
    blk = jnp.zeros((bm, bk), jnp.float32)
    for s in range(v_ref.shape[1]):
        blk = blk + jnp.where(c_ref[:, s:s + 1] == col,
                              v_ref[:, s:s + 1].astype(jnp.float32), 0.0)
    o_ref[...] += jnp.sum(blk * x_ref[...].astype(jnp.float32), axis=1,
                          keepdims=True)


def sparse_matvec(vals: Array, cols: Array, x: Array, *, bm: int = BM,
                  bk: int = BK, interpret: bool = True) -> Array:
    """y = A @ x with A in padded-ELL rows.  vals/cols: (m, L); x: (1, n)
    row → (m, 1) f32.  m and n must be multiples of bm and bk (``ops.py``
    pads rows with empty slots and x with zeros)."""
    m, L = vals.shape
    n = x.shape[1]
    assert m % bm == 0 and n % bk == 0, (vals.shape, x.shape, bm, bk)
    return pl.pallas_call(
        _spmv_kernel,
        grid=(m // bm, n // bk),
        in_specs=[
            pl.BlockSpec((bm, L), lambda i, kk: (i, 0)),
            pl.BlockSpec((bm, L), lambda i, kk: (i, 0)),
            pl.BlockSpec((1, bk), lambda i, kk: (0, kk)),
        ],
        out_specs=pl.BlockSpec((bm, 1), lambda i, kk: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, 1), jnp.float32),
        interpret=interpret,
        name="sparse_matvec",
    )(vals, cols, x)
