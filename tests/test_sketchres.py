"""Sketch-resident operators: maintained panels, folds, staleness,
reconstruction, and the Session/serve wiring of the fourth policy branch.

The load-bearing invariant is *linearity*: folding a drift into the
resident panels must agree with a fresh sketch of the drifted operand
drawn from the same seeds — the fold is exact, only staleness (coverage,
adaptivity, storage rounding) degrades the panels.  Everything here pins
that invariant and the policy built on top of it: zero-iteration
sketch-reconstruct answers are only ever served residual-probe-verified,
and a tripped staleness odometer falls back to a re-sketch plus a REAL
solve, never an unverified reconstruction.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import SVDSpec, Session, clear_plan_cache, trace_count
from repro.api.plan import plan as make_plan
from repro.core.operators import DenseOp, LowRankOp
from repro.sketchres import (BUDGET, apply_dense_delta, apply_entries,
                             apply_lowrank_delta, is_stale, pad_entries,
                             reconstruct, sketch_operand, staleness_ratio)

KEY = jax.random.PRNGKey(3)
SPEC = SVDSpec(method="gnystrom", rank=6, oversample=8)


def _lowrank(key, m, n, r, dtype=jnp.float32):
    ku, kv = jax.random.split(key)
    U = jax.random.normal(ku, (m, r))
    V = jax.random.normal(kv, (n, r))
    s = jnp.logspace(0.0, -2.0, r)
    return ((U * s) @ V.T).astype(dtype)


def _entries(rng, m, n, e, scale=1e-3):
    rows = rng.integers(0, m, e).astype(np.int32)
    cols = rng.integers(0, n, e).astype(np.int32)
    vals = (scale * rng.standard_normal(e)).astype(np.float32)
    return rows, cols, vals


def _coo_apply(A, rows, cols, vals):
    A2 = np.asarray(A).copy()
    np.add.at(A2, (np.asarray(rows), np.asarray(cols)), np.asarray(vals))
    return jnp.asarray(A2)


# --------------------------------------------------------------------------
# state + folds
# --------------------------------------------------------------------------

def test_sketch_operand_panels_match_dense_test_matrices():
    A = _lowrank(jax.random.PRNGKey(0), 40, 30, 6)
    st = sketch_operand(A, SPEC, key=KEY)
    om, ps = st.sketches()
    np.testing.assert_allclose(np.asarray(st.Y),
                               np.asarray(A @ om.dense()),
                               rtol=0, atol=1e-5)
    np.testing.assert_allclose(np.asarray(st.Z),
                               np.asarray(ps.dense().T @ A),
                               rtol=0, atol=1e-5)
    assert float(st.folded_mass) == 0.0
    assert float(st.base_norm) > 0.0


def test_apply_entries_matches_fresh_sketch_same_seeds():
    """The tentpole invariant: a fold IS the sketch of the drifted operand
    (same seeds), to f32/scatter roundoff."""
    rng = np.random.default_rng(1)
    A = _lowrank(jax.random.PRNGKey(1), 48, 36, 6)
    st = sketch_operand(A, SPEC, key=KEY)
    rows, cols, vals = _entries(rng, 48, 36, 300, scale=1e-2)
    folded = apply_entries(st, rows, cols, vals)
    fresh = sketch_operand(_coo_apply(A, rows, cols, vals), SPEC, key=KEY)
    scale = float(jnp.linalg.norm(fresh.Y))
    assert float(jnp.linalg.norm(folded.Y.astype(jnp.float32)
                                 - fresh.Y.astype(jnp.float32))) < 1e-5 * scale
    scale = float(jnp.linalg.norm(fresh.Z))
    assert float(jnp.linalg.norm(folded.Z.astype(jnp.float32)
                                 - fresh.Z.astype(jnp.float32))) < 1e-5 * scale


def test_apply_dense_delta_equals_entry_fold():
    rng = np.random.default_rng(2)
    A = _lowrank(jax.random.PRNGKey(2), 32, 24, 5)
    st = sketch_operand(A, SPEC, key=KEY)
    D = (1e-3 * rng.standard_normal((32, 24))).astype(np.float32)
    rr, cc = np.meshgrid(np.arange(32), np.arange(24), indexing="ij")
    via_entries = apply_entries(st, rr.ravel(), cc.ravel(), D.ravel())
    via_block = apply_dense_delta(st, jnp.asarray(D))
    np.testing.assert_allclose(np.asarray(via_entries.Y),
                               np.asarray(via_block.Y), rtol=0, atol=2e-5)
    np.testing.assert_allclose(np.asarray(via_entries.Z),
                               np.asarray(via_block.Z), rtol=0, atol=2e-5)
    # dense-block mass is the exact ‖D‖_F = ℓ2 of the entry values
    np.testing.assert_allclose(float(via_entries.folded_mass),
                               float(via_block.folded_mass), rtol=1e-5)


def test_apply_lowrank_delta_matches_materialized():
    k1, k2 = jax.random.split(jax.random.PRNGKey(4))
    A = _lowrank(k1, 40, 28, 5)
    st = sketch_operand(A, SPEC, key=KEY)
    U = jax.random.normal(k2, (40, 2))
    Vt = jax.random.normal(jax.random.PRNGKey(5), (2, 28))
    s = jnp.asarray([1e-3, 5e-4])
    dop = LowRankOp(U, s, Vt)
    via_op = apply_lowrank_delta(st, dop)
    via_dense = apply_dense_delta(st, (U * s) @ Vt)
    np.testing.assert_allclose(np.asarray(via_op.Y),
                               np.asarray(via_dense.Y), rtol=0, atol=1e-5)
    np.testing.assert_allclose(np.asarray(via_op.Z),
                               np.asarray(via_dense.Z), rtol=0, atol=1e-5)


def test_pallas_and_xla_backends_agree():
    rng = np.random.default_rng(3)
    A = _lowrank(jax.random.PRNGKey(6), 36, 30, 5)
    st_x = sketch_operand(A, SPEC, key=KEY, backend="xla")
    st_p = dataclasses.replace(st_x, backend="pallas")
    rows, cols, vals = _entries(rng, 36, 30, 200)
    fx = apply_entries(st_x, rows, cols, vals)
    fp = apply_entries(st_p, rows, cols, vals)
    np.testing.assert_allclose(np.asarray(fx.Y), np.asarray(fp.Y),
                               rtol=0, atol=1e-6)
    np.testing.assert_allclose(np.asarray(fx.Z), np.asarray(fp.Z),
                               rtol=0, atol=1e-6)


def test_sketch_operand_pallas_operand_matches_xla():
    """DenseOp(backend="pallas")'s one-sweep capture (the sketch kernel on
    both directions, A Ω without forming Aᵀ) equals the xla panels."""
    A = _lowrank(jax.random.PRNGKey(8), 44, 32, 6)
    st_x = sketch_operand(DenseOp(A), SPEC, key=KEY)
    st_p = sketch_operand(DenseOp(A, backend="pallas"), SPEC, key=KEY)
    for a, b in ((st_p.Y, st_x.Y), (st_p.Z, st_x.Z)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=0, atol=1e-5)


def test_pad_entries_is_exact_noop():
    rng = np.random.default_rng(4)
    A = _lowrank(jax.random.PRNGKey(7), 24, 20, 4)
    st = sketch_operand(A, SPEC, key=KEY)
    rows, cols, vals = _entries(rng, 24, 20, 37)
    pr, pc, pv = pad_entries(rows, cols, vals)
    assert pr.shape[0] == 64                       # quantum, pow2
    assert pad_entries(*_entries(rng, 24, 20, 65))[0].shape[0] == 128
    raw = apply_entries(st, rows, cols, vals)
    padded = apply_entries(st, pr, pc, pv)
    np.testing.assert_array_equal(np.asarray(raw.Y), np.asarray(padded.Y))
    np.testing.assert_array_equal(np.asarray(raw.Z), np.asarray(padded.Z))
    np.testing.assert_allclose(float(raw.folded_mass),
                               float(padded.folded_mass), rtol=1e-6)


def test_staleness_odometer_trips_and_only_then():
    A = _lowrank(jax.random.PRNGKey(8), 32, 24, 4)
    st = sketch_operand(A, SPEC, key=KEY)
    assert not bool(is_stale(st))
    # a fold of exactly budget*base_norm mass lands the ratio on 1.0
    target = float(st.budget * st.base_norm)
    small = apply_entries(st, [0], [0], [0.1 * target])
    assert not bool(is_stale(small))
    assert 0.0 < float(staleness_ratio(small)) < 1.0
    big = apply_entries(small, [1], [1], [target])
    assert bool(is_stale(big))
    assert float(staleness_ratio(big)) >= 1.0


def test_reconstruct_zero_iterations_and_accuracy():
    A = _lowrank(jax.random.PRNGKey(9), 60, 44, 6)
    st = sketch_operand(A, SPEC, key=KEY)
    f = reconstruct(st, SPEC)
    assert int(f.iterations) == 0
    assert f.method == "sketch"
    assert not bool(f.breakdown)
    Ahat = (f.U * f.s) @ f.V.T
    rel = float(jnp.linalg.norm(Ahat - A) / jnp.linalg.norm(A))
    assert rel < 1e-4                               # exact-rank operand


def test_reconstruct_tracks_folded_drift():
    """A rank-1 block shipped entry-by-entry: after the fold, reconstruct
    matches the drifted operand (still within the rank budget) and is far
    from the pre-drift one — the panels genuinely moved."""
    m, n = 60, 44
    A = _lowrank(jax.random.PRNGKey(10), m, n, 5)
    u = jax.random.normal(jax.random.PRNGKey(30), (m,))
    v = jax.random.normal(jax.random.PRNGKey(31), (n,))
    D = 0.05 * jnp.outer(u, v)                      # rank-1, ~5% mass
    st = sketch_operand(A, SPEC, key=KEY)
    rr, cc = np.meshgrid(np.arange(m), np.arange(n), indexing="ij")
    st = apply_entries(st, rr.ravel(), cc.ravel(), np.asarray(D).ravel())
    A2 = A + D
    f = reconstruct(st, SPEC)
    rel = float(jnp.linalg.norm((f.U * f.s) @ f.V.T - A2)
                / jnp.linalg.norm(A2))
    stale_rel = float(jnp.linalg.norm((f.U * f.s) @ f.V.T - A)
                      / jnp.linalg.norm(A))
    assert rel < 1e-3
    assert stale_rel > 10 * rel


# --------------------------------------------------------------------------
# plan staging
# --------------------------------------------------------------------------

def test_plan_sketch_fold_stages_per_padded_length():
    clear_plan_cache()
    rng = np.random.default_rng(6)
    A = _lowrank(jax.random.PRNGKey(11), 40, 32, 5)
    p = make_plan(SPEC, like=DenseOp(A))
    st = p.sketch(A, key=KEY)
    t0 = trace_count()
    for e in (10, 20, 33, 60):                      # all pad to 64
        rows, cols, vals = _entries(rng, 40, 32, e)
        st = p.sketch_fold(st, rows, cols, vals)
    assert trace_count() - t0 == 1                  # one padded length
    st = p.sketch_fold(st, *_entries(rng, 40, 32, 100))   # pads to 128
    assert trace_count() - t0 == 2
    f1 = p.sketch_reconstruct(st)
    t1 = trace_count()
    f2 = p.sketch_reconstruct(st)
    assert trace_count() == t1                      # cached executable
    assert int(f1.iterations) == int(f2.iterations) == 0


def test_plan_sketch_memoizes_per_operand_signature():
    clear_plan_cache()
    A = _lowrank(jax.random.PRNGKey(12), 40, 32, 5)
    p = make_plan(SPEC, like=DenseOp(A))
    p.sketch(A, key=KEY)
    t0 = trace_count()
    p.sketch(A + 1.0, key=jax.random.PRNGKey(99))   # same signature
    assert trace_count() == t0


# --------------------------------------------------------------------------
# Session: the fourth policy branch
# --------------------------------------------------------------------------

def _drift_step(rng, sess, m, n, e=48, scale=5e-4):
    rows, cols, vals = _entries(rng, m, n, e, scale=scale)
    fact = sess.entries(rows, cols, vals)
    return fact, sess.history[-1]


def test_session_entries_sketch_branch_zero_iterations():
    rng = np.random.default_rng(7)
    m, n = 48, 36
    A = _lowrank(jax.random.PRNGKey(13), m, n, 6)
    sess = Session(A, SVDSpec(method="fsvd", rank=6), key=KEY,
                   sketch_tol=5e-3)
    sess.solve()
    kinds = []
    for _ in range(4):
        fact, rec = _drift_step(rng, sess, m, n)
        kinds.append(rec["kind"])
        if rec["kind"] == "sketch":
            assert rec["iterations"] == 0
            assert rec["probe"] <= rec["gate"] == 5e-3
            assert 0.0 < rec["staleness"] < 1.0
    assert kinds.count("sketch") >= 3
    # parity: the final answer matches the dense SVD of the drifted
    # operand at the probe's accuracy scale
    s_true = np.linalg.svd(np.asarray(sess.op.A), compute_uv=False)[:6]
    err = float(np.max(np.abs(np.asarray(sess.fact.s) - s_true))
                / s_true[0])
    assert err < 5e-3
    assert sess.counts()["sketch"] == kinds.count("sketch")
    assert sess.meta()["sketches"] == kinds.count("sketch")


def test_session_entries_staleness_falls_back_to_real_solve():
    rng = np.random.default_rng(8)
    m, n = 40, 30
    A = _lowrank(jax.random.PRNGKey(14), m, n, 5)
    sess = Session(A, SVDSpec(method="fsvd", rank=5), key=KEY,
                   sketch_tol=1e-2)
    sess.solve()
    _drift_step(rng, sess, m, n)                    # sketch resident now
    # one huge batch trips the odometer
    fact, rec = _drift_step(rng, sess, m, n, e=600, scale=1.0)
    assert rec["kind"] in ("refine", "restart")     # a REAL solve
    assert rec["sketch_stale"] is True
    assert rec["staleness"] >= 1.0
    assert "probe" not in rec                       # never reconstructed
    # the re-sketch reset the odometer and tracks the post-drift operand
    assert sess.sketch is not None
    assert float(sess.sketch.folded_mass) == 0.0
    om, _ = sess.sketch.sketches()
    np.testing.assert_allclose(np.asarray(sess.sketch.Y),
                               np.asarray(sess.op.A @ om.dense()),
                               rtol=1e-3, atol=1e-3)


def test_session_entries_rejection_annotates_fallback():
    rng = np.random.default_rng(9)
    m, n = 40, 30
    A = _lowrank(jax.random.PRNGKey(15), m, n, 5)
    sess = Session(A, SVDSpec(method="fsvd", rank=5), key=KEY,
                   sketch_tol=1e-12)                # impossible gate
    sess.solve()
    fact, rec = _drift_step(rng, sess, m, n)
    assert rec["kind"] in ("refine", "restart")
    assert rec["sketch_rejected"] is True
    assert rec["probe"] > rec["gate"] == 1e-12


def test_session_entries_sketch_tol_zero_disables_path():
    rng = np.random.default_rng(10)
    m, n = 32, 24
    A = _lowrank(jax.random.PRNGKey(16), m, n, 4)
    sess = Session(A, SVDSpec(method="fsvd", rank=4), key=KEY,
                   sketch_tol=0.0)
    sess.solve()
    for _ in range(2):
        fact, rec = _drift_step(rng, sess, m, n)
        assert rec["kind"] in ("refine", "restart")
    assert sess.sketch is None                      # never even built
    assert "sketch" not in sess.counts()


def test_session_entries_requires_dense_operand():
    U = jax.random.normal(jax.random.PRNGKey(17), (20, 3))
    Vt = jax.random.normal(jax.random.PRNGKey(18), (3, 16))
    sess = Session(LowRankOp(U, jnp.ones(3), Vt),
                   SVDSpec(method="fsvd", rank=3), key=KEY)
    with pytest.raises(TypeError, match="dense operand"):
        sess.entries([0], [0], [1.0])
    with pytest.raises(ValueError, match="equal lengths"):
        Session(jnp.ones((8, 8)), SVDSpec(method="fsvd", rank=2),
                key=KEY).entries([0, 1], [0], [1.0])


def test_session_delta_keeps_resident_sketch_live():
    """A structured delta between entry batches folds into the resident
    panels (sketch linearity) instead of invalidating them."""
    rng = np.random.default_rng(11)
    m, n = 40, 30
    A = _lowrank(jax.random.PRNGKey(19), m, n, 5)
    sess = Session(A, SVDSpec(method="fsvd", rank=5), key=KEY,
                   sketch_tol=1e-2)
    sess.solve()
    _drift_step(rng, sess, m, n)                    # sketch resident
    U = jax.random.normal(jax.random.PRNGKey(20), (m, 1))
    Vt = jax.random.normal(jax.random.PRNGKey(21), (1, n))
    sess.delta(LowRankOp(U, jnp.asarray([1e-4]), Vt))
    assert sess.sketch is not None
    om, _ = sess.sketch.sketches()
    np.testing.assert_allclose(np.asarray(sess.sketch.Y),
                               np.asarray(sess.op.A @ om.dense()),
                               rtol=1e-3, atol=1e-3)
    # wholesale replacement drops it
    sess.update(jnp.asarray(sess.op.A) + 0.0)
    assert sess.sketch is None


# --------------------------------------------------------------------------
# satellite 2: accepted paths annotate their gate value
# --------------------------------------------------------------------------

def test_accepted_update_and_sketch_records_carry_gate():
    rng = np.random.default_rng(12)
    m, n = 48, 36
    A = _lowrank(jax.random.PRNGKey(22), m, n, 5)
    sess = Session(A, SVDSpec(method="fsvd", rank=5), key=KEY,
                   update_tol=1e-3, sketch_tol=5e-3)
    sess.solve()
    # accepted rank-1 update
    U = jax.random.normal(jax.random.PRNGKey(23), (m, 1))
    Vt = jax.random.normal(jax.random.PRNGKey(24), (1, n))
    sess.delta(LowRankOp(U, jnp.asarray([1e-6]), Vt))
    upd = sess.history[-1]
    assert upd["kind"] == "update"
    assert upd["gate"] == 1e-3 and upd["residual_update"] <= 1e-3
    # accepted sketch-reconstruct
    for _ in range(3):
        fact, rec = _drift_step(rng, sess, m, n, e=32, scale=2e-4)
        if rec["kind"] == "sketch":
            break
    assert rec["kind"] == "sketch"
    assert rec["gate"] == 5e-3 and rec["probe"] <= 5e-3
    # meta() round-trips the annotations as plain JSON scalars
    import json
    hist = sess.meta()["history"]
    json.dumps(hist)
    assert any("gate" in r for r in hist)


# --------------------------------------------------------------------------
# satellite 1: spec validation for the sketch solvers
# --------------------------------------------------------------------------

def test_spec_rejects_rbk_zero_passes():
    with pytest.raises(ValueError, match="at least one pass"):
        SVDSpec(method="rbk", passes=0)
    SVDSpec(method="rbk", passes=1)                 # fine
    SVDSpec(method="gnystrom", passes=0)            # sketch-only regime


@pytest.mark.parametrize("method", ["rbk", "gnystrom"])
def test_spec_rejects_sketch_dim_below_rank(method):
    with pytest.raises(ValueError, match="sketch_dim"):
        SVDSpec(method=method, rank=8, sketch_dim=4)
    SVDSpec(method=method, rank=8, sketch_dim=8)    # boundary is legal
    SVDSpec(method="fsvd", rank=8, sketch_dim=4)    # other methods: no-op
