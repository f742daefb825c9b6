"""SketchState — a resident generalized-Nyström sketch pair, maintained
incrementally under drift.

Both panels are *linear* in the operand: ``Y = AΩ`` and ``Z = ΨᵀA``, so
for any drift ``A → A + Δ`` the panels of the new operand are exactly
``Y + ΔΩ`` and ``Z + ΨᵀΔ`` — no approximation in the fold itself.  For a
COO entry stream that is a hashed scatter-add: entry ``(i, j, v)`` lands
``v·Ω[j, :]`` on row i of Y and ``v·Ψ[i, :]`` on column j of Z, and with
the hashed-sign ensemble below each of those is ζ signed slot updates —
``kernels/count_sketch.scatter_add`` territory, O(nnz·ζ) per fold.

Test matrices here are the **hashed-sign** (count-sketch / Clarkson–
Woodruff) ensemble: each *source* coordinate ``j`` owns ζ hash slots
``slots[j, s] ∈ [0, d)`` with signs ±1/√ζ.  This is the transpose layout
of ``core.sketch.SparseSignSketch`` (which packs ζ source rows per
*sketch* coordinate, the gather-friendly direction): streaming folds need
to answer "which sketch slots does source j touch?" in O(ζ), which is
exactly what the per-source layout stores.  ``E[TTᵀ] = I`` still holds
(independent signs), so the ensemble is an oblivious subspace embedding
like its gather twin.  Slots/signs are regenerated **in-trace from the
stored PRNG seeds** on every fold/reconstruct — the state ships two keys
instead of two index tables, so checkpoints and cross-process transport
stay panel-sized.

Why a staleness trip at all, when the folds are exact?  Three reasons the
maintained panels can stop being as good as a fresh sketch: (i) the
obliviousness argument needs Ω/Ψ independent of the data — a long
*adaptive* entry stream is correlated with the realized test matrices and
can concentrate mass in directions they under-sample; (ii) under bf16
storage every fold re-rounds the panels, so panel noise grows with folded
mass; (iii) drift can raise the effective rank past what the ``k = r+p``
oversampling covers.  All three grow with the cumulative folded Frobenius
mass, so the state tracks ``folded_mass`` (an upper bound/estimate of
``Σ‖Δ‖_F``) against ``budget·base_norm`` — when it trips, the owner must
re-sketch from the operand (one sweep) instead of trusting the panels.
The per-answer accuracy gate stays the residual probe; the trip is the
a-priori guard that keeps un-probe-able garbage from ever being built.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.api.results import Factorization
from repro.core.gk import _store_dtype
from repro.core.operators import HIGHEST, as_operator, register_operator
from repro.core.sketch import _panel_dims, nystrom_reconstruct
from repro.kernels.sketch_matvec import ZETA

Array = jax.Array

# default staleness budget: re-sketch once the cumulative folded Frobenius
# mass reaches half the operand's mass at sketch time.  Conservative for
# (i)/(iii) above and far below where bf16 re-rounding (ii) accumulates.
BUDGET = 0.5

# fold batches are padded up to a multiple of this (and then to the next
# power of two) so the plan cache sees O(log E) distinct entry shapes.
_ENTRY_QUANTUM = 64


# ---------------------------------------------------------------------------
# hashed-sign ensemble (per-source-coordinate layout)
# ---------------------------------------------------------------------------

def _hashed(key: Array, n: int, d: int, zeta: int
            ) -> tuple[Array, Array]:
    """slots (n, ζ) in [0, d) and signs (n, ζ) = ±1/√ζ, in-trace."""
    z = max(1, min(zeta, d))
    ki, ks = jax.random.split(key)
    slots = jax.random.randint(ki, (n, z), 0, d, jnp.int32)
    signs = jax.random.rademacher(ks, (n, z), jnp.float32) / jnp.sqrt(
        jnp.asarray(float(z), jnp.float32))
    return slots, signs


def _dense(slots: Array, signs: Array, d: int) -> Array:
    """Materialize T (n, d) f32 — collisions sum, matching the fold."""
    n, z = slots.shape
    T = jnp.zeros((n, d), jnp.float32)
    return T.at[jnp.arange(n)[:, None], slots].add(signs)


@dataclasses.dataclass(frozen=True)
class _HashedSketch:
    """Duck-types ``core.sketch``'s test matrices (shape/dense/tapply/
    rapply) so ``Operator.sketch_pass`` — including DenseOp's fused path —
    accepts the streaming ensemble for the initial one-sweep capture."""

    slots: Array
    signs: Array
    d: int

    @property
    def shape(self) -> tuple[int, int]:
        return (self.slots.shape[0], self.d)

    def dense(self) -> Array:
        return _dense(self.slots, self.signs, self.d)

    def tapply(self, X: Array) -> Array:
        return jnp.dot(self.dense().T, X.astype(jnp.float32),
                       precision=HIGHEST, preferred_element_type=jnp.float32)

    def rapply(self, X: Array) -> Array:
        return jnp.dot(X.astype(jnp.float32), self.dense(),
                       precision=HIGHEST, preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# the state
# ---------------------------------------------------------------------------

@register_operator
@dataclasses.dataclass(frozen=True, eq=False)
class SketchState:
    """Resident sketch pair + seeds + staleness odometer (a pytree).

    Y           (m, k) range panel ``AΩ``, storage dtype (bf16 under
                ``precision="bf16"``; every fold accumulates f32).
    Z           (l, n) co-range panel ``ΨᵀA``, storage dtype.
    okey/pkey   PRNG seeds of the hashed-sign Ω (n→k) / Ψ (m→l); the
                slot/sign tables are re-derived in-trace per fold.
    folded_mass () f32 — cumulative ‖Δ‖_F folded since the last sweep
                (exact ℓ2 of the values for entry folds, the ‖ΨᵀΔ‖_F
                sketch estimate for block folds).
    base_norm   () f32 — ‖A‖_F estimate at sweep time (``‖Z‖_F``, the
                same unbiased sketch estimator).
    """

    Y: Array
    Z: Array
    okey: Array
    pkey: Array
    folded_mass: Array
    base_norm: Array
    zeta: int = ZETA
    budget: float = BUDGET
    backend: str = "xla"

    _data_fields = ("Y", "Z", "okey", "pkey", "folded_mass", "base_norm")
    _meta_fields = ("zeta", "budget", "backend")

    @property
    def shape(self) -> tuple[int, int]:
        return (self.Y.shape[0], self.Z.shape[1])

    @property
    def panel_dims(self) -> tuple[int, int]:
        """(k, l) — range / co-range sketch widths."""
        return (self.Y.shape[1], self.Z.shape[0])

    def sketches(self) -> tuple[_HashedSketch, _HashedSketch]:
        """(Ω, Ψ) re-derived from the stored seeds."""
        m, n = self.shape
        k, l = self.panel_dims
        return (_HashedSketch(*_hashed(self.okey, n, k, self.zeta), k),
                _HashedSketch(*_hashed(self.pkey, m, l, self.zeta), l))


def sketch_operand(A, spec, *, key: Array, budget: float = BUDGET,
                   backend: str | None = None) -> SketchState:
    """ONE sweep over the operand → a resident :class:`SketchState` sized
    by the spec's gnystrom panel rule (``k = rank+oversample`` or
    ``sketch_dim``, ``l ≈ 2k``)."""
    A = as_operator(A)
    m, n = A.shape
    k, l = _panel_dims(spec.rank, spec.oversample, spec.sketch_dim, m, n)
    store = _store_dtype(spec.precision,
                         jnp.promote_types(A.dtype, jnp.float32))
    okey, pkey = jax.random.split(key)
    om = _HashedSketch(*_hashed(okey, n, k, ZETA), k)
    ps = _HashedSketch(*_hashed(pkey, m, l, ZETA), l)
    Y, Z = A.sketch_pass(om, ps)                  # the one operator sweep
    Zt = Z.astype(jnp.float32).T                  # (l, n) = ΨᵀA
    base = jnp.linalg.norm(Zt)                    # E‖ΨᵀA‖_F² = ‖A‖_F²
    return SketchState(Y=Y.astype(store), Z=Zt.astype(store),
                       okey=okey, pkey=pkey,
                       folded_mass=jnp.zeros((), jnp.float32),
                       base_norm=base, zeta=ZETA, budget=budget,
                       backend=backend or spec.backend)


# ---------------------------------------------------------------------------
# incremental folds
# ---------------------------------------------------------------------------

def _scatter(rows: Array, cols: Array, vals: Array,
             shape: tuple[int, int], backend: str) -> Array:
    if backend == "pallas":
        from repro.kernels import ops as kops
        return kops.scatter_add(rows, cols, vals, shape)
    return jnp.zeros(shape, jnp.float32).at[rows, cols].add(
        vals.astype(jnp.float32))


def pad_entries(rows, cols, vals, *, quantum: int = _ENTRY_QUANTUM
                ) -> tuple[Array, Array, Array]:
    """Pad a COO batch to a compile-friendly length (next power-of-two
    multiple of ``quantum``) with (0, 0, 0.0) entries — exact no-ops for
    both the fold and the mass odometer — so streaming callers hit the
    plan cache O(log E) times instead of once per distinct batch size."""
    rows = jnp.asarray(rows, jnp.int32).reshape(-1)
    cols = jnp.asarray(cols, jnp.int32).reshape(-1)
    vals = jnp.asarray(vals, jnp.float32).reshape(-1)
    E = rows.shape[0]
    target = quantum
    while target < E:
        target *= 2
    pad = target - E
    if pad:
        rows = jnp.pad(rows, (0, pad))
        cols = jnp.pad(cols, (0, pad))
        vals = jnp.pad(vals, (0, pad))
    return rows, cols, vals


def apply_entries(state: SketchState, rows: Array, cols: Array,
                  vals: Array) -> SketchState:
    """Fold a COO entry stream of the drift into both panels, O(nnz·ζ).

    Entry ``(i, j, v)`` contributes ``v·Ω[j, :]`` to ``Y[i, :]`` and
    ``v·Ψ[i, :]`` to ``Z[:, j]`` — with the hashed-sign ensemble each is
    ζ signed slot updates, landed by the count-sketch scatter-add kernel
    (duplicate destinations sum, so repeated coordinates in the stream
    are folded faithfully).  Zero-value entries are exact no-ops, which
    makes :func:`pad_entries` padding safe.
    """
    m, n = state.shape
    k, l = state.panel_dims
    om, ps = state.sketches()
    z = om.slots.shape[1]
    rows = jnp.asarray(rows, jnp.int32).reshape(-1)
    cols = jnp.asarray(cols, jnp.int32).reshape(-1)
    vals = jnp.asarray(vals, jnp.float32).reshape(-1)
    # ΔY: destination rows are the entries' rows, columns their hashed
    # Ω slots (slot-major concatenation over the ζ expansions).
    dY = _scatter(jnp.tile(rows, z), om.slots[cols].T.reshape(-1),
                  (vals[None, :] * om.signs[cols].T).reshape(-1),
                  (m, k), state.backend)
    # ΔZ: destination rows are the entries' hashed Ψ slots, columns the
    # entries' columns.
    dZ = _scatter(ps.slots[rows].T.reshape(-1), jnp.tile(cols, z),
                  (vals[None, :] * ps.signs[rows].T).reshape(-1),
                  (l, n), state.backend)
    Y = (state.Y.astype(jnp.float32) + dY).astype(state.Y.dtype)
    Z = (state.Z.astype(jnp.float32) + dZ).astype(state.Z.dtype)
    mass = state.folded_mass + jnp.linalg.norm(vals)
    return dataclasses.replace(state, Y=Y, Z=Z, folded_mass=mass)


def _apply_block(state: SketchState, dop, mass: Array) -> SketchState:
    om, ps = state.sketches()
    dY = dop.matmat(om.dense())                       # (m, k) = ΔΩ
    dZ = dop.rmatmat(ps.dense()).astype(jnp.float32).T  # (l, n) = ΨᵀΔ
    Y = (state.Y.astype(jnp.float32) + dY.astype(jnp.float32)
         ).astype(state.Y.dtype)
    Z = (state.Z.astype(jnp.float32) + dZ).astype(state.Z.dtype)
    return dataclasses.replace(state, Y=Y, Z=Z,
                               folded_mass=state.folded_mass + mass)


def apply_dense_delta(state: SketchState, D: Array) -> SketchState:
    """Fold a dense (m, n) drift block: one panel GEMM per sketch, exact
    Frobenius mass on the odometer."""
    D = jnp.asarray(D)
    return _apply_block(state, as_operator(D),
                        jnp.linalg.norm(D.astype(jnp.float32)))


def apply_lowrank_delta(state: SketchState, dop) -> SketchState:
    """Fold a factored drift (``LowRankOp`` or any operator) without
    materializing it: two factored panel products.  The mass odometer
    takes the ``‖ΨᵀΔ‖_F`` sketch estimate (same estimator as
    ``base_norm``, no materialization)."""
    dop = as_operator(dop)
    om, ps = state.sketches()
    dY = dop.matmat(om.dense())
    dZ = dop.rmatmat(ps.dense()).astype(jnp.float32).T
    Y = (state.Y.astype(jnp.float32) + dY.astype(jnp.float32)
         ).astype(state.Y.dtype)
    Z = (state.Z.astype(jnp.float32) + dZ).astype(state.Z.dtype)
    return dataclasses.replace(
        state, Y=Y, Z=Z,
        folded_mass=state.folded_mass + jnp.linalg.norm(dZ))


# ---------------------------------------------------------------------------
# staleness + reconstruction
# ---------------------------------------------------------------------------

def staleness_ratio(state: SketchState) -> Array:
    """Folded mass over the coverage budget — ≥ 1.0 means stale."""
    return state.folded_mass / jnp.maximum(
        jnp.asarray(state.budget, jnp.float32) * state.base_norm, 1e-30)


def is_stale(state: SketchState) -> Array:
    """True once the cumulative folded mass exceeds the coverage budget;
    owners must re-sketch from the operand instead of reconstructing."""
    return staleness_ratio(state) >= 1.0


def reconstruct(state: SketchState, spec) -> Factorization:
    """Zero-sweep factorization from the maintained panels: the PR 9
    stabilized-pinv generalized-Nyström core solve on ``(Y, Z, ΨᵀY)``.
    Returns ``iterations=0, method="sketch"`` — by construction nothing
    here touches the operator, so callers MUST gate the answer (residual
    probe + :func:`is_stale`) before serving it."""
    _, ps = state.sketches()
    Yf = state.Y.astype(jnp.float32)
    C = ps.tapply(Yf)                             # (l, k) = ΨᵀY, no touch
    U, s, Vt = nystrom_reconstruct(Yf, state.Z, C)
    r = min(spec.rank, s.shape[0])
    return Factorization(U[:, :r], s[:r], Vt[:r, :].T,
                         iterations=jnp.asarray(0, jnp.int32),
                         breakdown=jnp.asarray(False), method="sketch")


__all__ = [
    "BUDGET", "SketchState", "apply_dense_delta", "apply_entries",
    "apply_lowrank_delta", "is_stale", "pad_entries", "reconstruct",
    "sketch_operand", "staleness_ratio",
]
