"""Algorithm 1 invariants: orthonormal bases, the bidiagonal identity,
breakdown-based rank detection, host/in-graph agreement."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import make_lowrank
from repro.core import gk_bidiag, gk_bidiag_host
from repro.core.operators import DenseOp
from repro.core.tridiag import btb_tridiagonal


def bidiag_matrix(res, k):
    """Assemble B_{k+1,k} from the stored scalars."""
    B = np.zeros((k + 1, k))
    al = np.asarray(res.alphas)
    be = np.asarray(res.betas)
    for i in range(k):
        B[i, i] = al[i]
        B[i + 1, i] = be[i]
    return B


@pytest.mark.parametrize("runner", [gk_bidiag, gk_bidiag_host])
@pytest.mark.parametrize("m,n", [(120, 80), (64, 150)])
def test_orthonormal_bases(rng, runner, m, n):
    A = jax.random.normal(rng, (m, n))
    k = 40
    res = runner(A, k)
    kp = int(res.kprime)
    P = np.asarray(res.P[:, :kp])
    Q = np.asarray(res.Q[:, :kp + 1])
    np.testing.assert_allclose(P.T @ P, np.eye(kp), atol=5e-5)
    np.testing.assert_allclose(Q[:, :kp].T @ Q[:, :kp], np.eye(kp),
                               atol=5e-5)


@pytest.mark.parametrize("runner", [gk_bidiag, gk_bidiag_host])
def test_bidiag_identity(rng, runner):
    """A P_k = Q_{k+1} B_{k+1,k} (paper eq. 10)."""
    m, n, k = 90, 70, 25
    A = jax.random.normal(rng, (m, n))
    res = runner(A, k)
    kp = int(res.kprime)
    B = bidiag_matrix(res, kp)
    lhs = np.asarray(A) @ np.asarray(res.P[:, :kp])
    rhs = np.asarray(res.Q[:, :kp + 1]) @ B[:kp + 1, :kp]
    np.testing.assert_allclose(lhs, rhs, atol=2e-3)


@pytest.mark.parametrize("runner", [gk_bidiag, gk_bidiag_host])
@pytest.mark.parametrize("rank", [5, 17])
def test_breakdown_detects_rank(rng, runner, rank):
    """Krylov breakdown fires within a couple of iterations of the numerical
    rank (paper Table 1a: 102-105 iterations for rank-100 inputs)."""
    A = make_lowrank(rng, 100, 80, rank)
    res = runner(A, 60)
    assert bool(res.breakdown)
    assert rank <= int(res.kprime) <= rank + 3


def test_host_and_graph_agree(rng):
    rank = 10
    A = make_lowrank(rng, 80, 60, rank)
    r1 = gk_bidiag(A, 30, key=jax.random.PRNGKey(7))
    r2 = gk_bidiag_host(A, 30, key=jax.random.PRNGKey(7))
    assert int(r1.kprime) == int(r2.kprime)
    kp = int(r1.kprime)
    # the final direction at breakdown is roundoff-dominated (it spans the
    # exhausted complement); compare only the converged entries + top Ritz
    np.testing.assert_allclose(np.asarray(r1.alphas[:kp - 1]),
                               np.asarray(r2.alphas[:kp - 1]), rtol=2e-3)
    t1 = np.linalg.eigvalsh(np.asarray(btb_tridiagonal(r1.alphas, r1.betas)))
    t2 = np.linalg.eigvalsh(np.asarray(btb_tridiagonal(r2.alphas, r2.betas)))
    np.testing.assert_allclose(t1[-rank:], t2[-rank:], rtol=1e-2)


def test_start_vector_convention(rng):
    """Paper line 1: q1 ~ N(2, 1) — mean ~2 (sanity on the odd convention)."""
    from repro.core.gk import start_vector
    v = start_vector(rng, 10000)
    assert 1.9 < float(v.mean()) < 2.1


def _old_carry_gk_bidiag(A, k, *, key, reorth_passes=2):
    """The seed's fori_loop with whole-buffer ``jnp.where`` carries.

    Step math is shared with the production implementation (``gk._step`` /
    ``gk._rstep``), so comparing against ``gk_bidiag`` isolates exactly the
    carry rewrite (masked per-column ``dynamic_update_slice``) — which must
    be a pure traffic optimization, bit-for-bit.
    """
    from repro.core import gk as G
    from repro.core.operators import as_operator
    op = as_operator(A)
    m, n = op.shape
    dtype = jnp.float32
    q1 = G.start_vector(key, m, dtype)
    beta1 = jnp.linalg.norm(q1)
    q = q1 / beta1
    p = op.rmv(q).astype(dtype)
    alpha1 = jnp.linalg.norm(p)
    p = p / jnp.where(alpha1 > 0, alpha1, 1.0)
    Q = jnp.zeros((m, k + 1), dtype).at[:, 0].set(q)
    P = jnp.zeros((n, k), dtype).at[:, 0].set(p)
    alphas = jnp.zeros((k,), dtype).at[0].set(alpha1)
    betas = jnp.zeros((k,), dtype)
    eff = max(1e-8, 40.0 * float(jnp.finfo(dtype).eps))
    thresh = eff * jnp.maximum(alpha1, 1.0)

    def body(i, c):
        Qb, Pb, al, be, qv, pv, kp, done = c
        u, beta = G._step(op, pv, qv, al[i - 1], Qb, reorth_passes)
        hit = beta < thresh
        done1 = jnp.logical_or(done, hit)
        qn = u / jnp.where(beta > 0, beta, 1.0)
        v, alpha = G._rstep(op, qn, pv, beta, Pb, reorth_passes)
        done2 = jnp.logical_or(done1, alpha < thresh)
        pn = v / jnp.where(alpha > 0, alpha, 1.0)
        keep = jnp.logical_not(done1)
        keep2 = jnp.logical_not(done2)
        Qn = jnp.where(keep, Qb.at[:, i].set(qn).astype(dtype), Qb)
        Pn = jnp.where(keep2, Pb.at[:, i].set(pn), Pb)
        al_n = jnp.where(keep2, al.at[i].set(alpha), al)
        be_n = jnp.where(keep, be.at[i - 1].set(beta), be)
        kp_n = jnp.where(done2, kp, kp + 1)
        return (Qn, Pn, al_n, be_n, jnp.where(keep, qn, qv),
                jnp.where(keep2, pn, pv), kp_n, done2)

    c = jax.lax.fori_loop(1, k, body,
                          (Q, P, alphas, betas, q, p,
                           jnp.asarray(1, jnp.int32), jnp.asarray(False)))
    Qb, Pb, al, be, qv, pv, kp, done = c
    u, beta = G._step(op, pv, qv, al[kp - 1], Qb, reorth_passes)
    valid = jnp.logical_not(done) & (beta >= thresh)
    qn = u / jnp.where(beta > 0, beta, 1.0)
    Qf = jnp.where(valid, Qb.at[:, kp].set(qn.astype(dtype)), Qb)
    be_f = jnp.where(valid, be.at[kp - 1].set(beta), be)
    return G.GKResult(al, be_f, beta1, Pb, Qf, kp, done)


@pytest.mark.parametrize("case", ["fullrank", "breakdown"])
def test_column_carry_bit_equal_old_carry(case):
    """The masked per-column dynamic_update_slice carry is bit-identical to
    the seed's whole-buffer jnp.where carry — including when breakdown
    masking freezes the buffers mid-loop."""
    key = jax.random.PRNGKey(42)
    if case == "fullrank":
        A = jax.random.normal(key, (100, 70))
        k = 25
    else:
        A = make_lowrank(key, 100, 80, 8)       # breakdown around i=8-11
        k = 30
    new = gk_bidiag(A, k, key=jax.random.PRNGKey(7))
    old = _old_carry_gk_bidiag(A, k, key=jax.random.PRNGKey(7))
    for name in new._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(new, name)), np.asarray(getattr(old, name)),
            err_msg=f"carry rewrite changed GKResult.{name}")


@pytest.mark.parametrize("runner", [gk_bidiag, gk_bidiag_host])
def test_pallas_fused_step_matches_xla(rng, runner):
    """DenseOp(backend='pallas') routes the whole half-iteration through
    the fused gk_step kernels; the bases/recurrence must match the xla
    composition to f32 blocking-order accuracy."""
    from repro.core.operators import DenseOp
    A = jax.random.normal(rng, (120, 90))
    k = 20
    r_x = runner(DenseOp(A), k, key=jax.random.PRNGKey(3))
    r_p = runner(DenseOp(A, backend="pallas"), k, key=jax.random.PRNGKey(3))
    assert int(r_x.kprime) == int(r_p.kprime)
    np.testing.assert_allclose(np.asarray(r_x.alphas),
                               np.asarray(r_p.alphas), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(r_x.betas),
                               np.asarray(r_p.betas), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(r_x.Q), np.asarray(r_p.Q),
                               rtol=5e-4, atol=5e-4)


@pytest.mark.parametrize("runner", [gk_bidiag, gk_bidiag_host])
def test_bf16_precision_basis(rng, runner):
    """precision='bf16' stores the bases half-width; the recurrence scalars
    stay f32 and track the full-precision run to bf16 accuracy."""
    A = jax.random.normal(rng, (120, 90))
    k = 15
    full = runner(A, k, key=jax.random.PRNGKey(5))
    half = runner(A, k, key=jax.random.PRNGKey(5), precision="bf16")
    assert half.Q.dtype == jnp.bfloat16 and half.P.dtype == jnp.bfloat16
    assert half.alphas.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(half.alphas),
                               np.asarray(full.alphas), rtol=0.05, atol=0.05)
    # bf16-stored basis columns stay orthonormal to storage accuracy
    Q = np.asarray(half.Q, np.float32)
    np.testing.assert_allclose(Q.T @ Q, np.eye(Q.shape[1]), atol=0.05)


def test_fused_matvec_linop_equivalence(rng):
    """LinOp default fused path == explicit composition."""
    A = jax.random.normal(rng, (50, 40))
    op = DenseOp(A)
    p = jax.random.normal(jax.random.PRNGKey(1), (40,))
    y = jax.random.normal(jax.random.PRNGKey(2), (50,))
    np.testing.assert_allclose(np.asarray(op.mv_fused(p, y, 0.5)),
                               np.asarray(A @ p - 0.5 * y), rtol=1e-5)


# --- the host loop's compiled steps against the eager loop they replace ----

def _old_host_loop(op, k, *, q1, precision=None, callback=None,
                   reorth_passes=2, eps=1e-8):
    """The host loop as it ran before its half-steps were compiled: every
    half-step, normalization and basis write an eager op, both scalars
    read in one ``device_get``, the closing half-step when no breakdown
    came up to ``k``."""
    from repro.core import gk as G
    from repro.core.operators import as_operator
    op = as_operator(op)
    m, n = op.shape
    k = min(k, m, n)
    dtype = jnp.promote_types(op.dtype, jnp.float32)
    store = G._store_dtype(precision, dtype)
    q1 = q1.astype(dtype)
    beta1 = jnp.linalg.norm(q1)
    q = q1 / beta1
    p = op.rmv(q).astype(dtype)
    alpha1 = float(jnp.linalg.norm(p))
    thresh = G._eff_eps(eps, dtype, store) * max(alpha1, 1.0)
    p = p / (alpha1 if alpha1 > 0 else 1.0)
    qs, ps, al, be = [q], [p], [alpha1], []
    Qm = jnp.zeros((m, k + 1), store).at[:, 0].set(q.astype(store))
    Pm = jnp.zeros((n, k), store).at[:, 0].set(p.astype(store))
    breakdown = False
    for _ in range(1, k):
        u, beta_d = G._step(op, ps[-1], qs[-1], al[-1], Qm, reorth_passes)
        u = u.astype(dtype)
        qn = u / jnp.where(beta_d > 0, beta_d, 1.0).astype(dtype)
        v, alpha_d = G._rstep(op, qn, ps[-1], beta_d, Pm, reorth_passes)
        v = v.astype(dtype)
        beta, alpha = (float(x) for x in jax.device_get((beta_d, alpha_d)))
        if callback is not None:
            callback.on_step(len(al), alpha=alpha, beta=beta)
        if beta < thresh:
            breakdown = True
            break
        if alpha < thresh:
            be.append(beta)
            Qm = Qm.at[:, len(qs)].set(qn.astype(store))
            qs.append(qn)
            breakdown = True
            break
        pn = v / alpha
        Qm = Qm.at[:, len(qs)].set(qn.astype(store))
        Pm = Pm.at[:, len(ps)].set(pn.astype(store))
        qs.append(qn)
        ps.append(pn)
        al.append(alpha)
        be.append(beta)
    if not breakdown and len(al) == k:
        u, beta_d = G._step(op, ps[-1], qs[-1], al[-1], Qm, reorth_passes)
        beta = float(beta_d)
        if beta >= thresh:
            be.append(beta)
            Qm = Qm.at[:, k].set((u / beta).astype(store))
    kp = len(al)
    alphas = jnp.zeros((k,), dtype).at[:kp].set(jnp.asarray(al, dtype))
    betas = jnp.zeros((k,), dtype).at[:len(be)].set(jnp.asarray(be, dtype))
    return G.GKResult(alphas, betas, beta1, Pm, Qm,
                      jnp.asarray(kp, jnp.int32), jnp.asarray(breakdown))


def _corner(key, m, n, r, c):
    """An (m, n) operand that is zero outside its top-left (r, c) block,
    ``U diag(s) Vᵀ`` with σ from 3 down to 1.  Zero rows and columns stay
    exactly zero through the recurrence, so breakdown comes exactly where
    a subspace runs out and every stored scalar is well above rounding."""
    ku, kv = jax.random.split(key)
    d = min(r, c)
    U, _ = jnp.linalg.qr(jax.random.normal(ku, (r, d)))
    V, _ = jnp.linalg.qr(jax.random.normal(kv, (c, d)))
    block = (U * jnp.linspace(3.0, 1.0, d)) @ V.T
    return jnp.zeros((m, n)).at[:r, :c].set(block)


def _exit_case(exit_):
    """``(A, k, q1)`` whose host loop ends on the given exit.

    ``beta``: q1 lies in a 6-dim range, so the left space runs out first;
    ``alpha``: the row space (6 columns) runs out while q1's part outside
    the range keeps the left space open; ``none``: full rank, k = 10."""
    from repro.core.gk import start_vector
    key = jax.random.PRNGKey(3)
    q1 = start_vector(jax.random.PRNGKey(7), 64)
    if exit_ == "beta":
        return _corner(key, 64, 40, 6, 6), 16, q1.at[6:].set(0.0)
    if exit_ == "alpha":
        return _corner(key, 64, 40, 64, 6), 16, q1
    return _corner(key, 64, 40, 64, 40), 10, q1


# Agreement of the scalars (relative to the largest of them) and of the
# stored columns: the rounding of one compiled program against eager ops or
# the in-graph loop.  Measured on these cases: scalars 5.0e-7 (f32) and
# 1.5e-6 (bf16 bases), columns 9.8e-7 (f32) and one bf16 ulp.
SCALAR_TOL = {None: 1e-6, "bf16": 1e-5}
BASIS_TOL = {None: 1e-5, "bf16": 2.0 ** -8}


@pytest.mark.parametrize("closure", [False, True], ids=["DenseOp", "LinOp"])
@pytest.mark.parametrize("precision", [None, "bf16"], ids=["f32", "bf16"])
@pytest.mark.parametrize("exit_", ["beta", "alpha", "none"])
def test_host_loop_matches_eager_loop_and_graph(exit_, precision, closure):
    """The compiled host loop (DenseOp) and its eager body (LinOp) against
    the eager loop it replaced and the in-graph loop, on each of the three
    exits: the same iterations, scalars, written columns and zero columns
    beyond them, and the same ``on_step`` calls."""
    from repro.api.callbacks import RecordingCallback
    from repro.core.linop import LinOp
    A, k, q1 = _exit_case(exit_)
    op = (LinOp(A.shape, lambda p: A @ p, lambda q: A.T @ q, A.dtype)
          if closure else DenseOp(A))
    ref_cb, new_cb = RecordingCallback(), RecordingCallback()
    ref = _old_host_loop(op, k, q1=q1, precision=precision, callback=ref_cb)
    new = gk_bidiag_host(op, k, q1=q1, precision=precision, callback=new_cb)
    graph = gk_bidiag(op, k, q1=q1, precision=precision)

    kp = int(new.kprime)
    n_beta = int(np.count_nonzero(np.asarray(new.betas)))
    # the exit taken: β stored for every accepted α but the breaking one
    # (beta), for each (alpha), or for each plus β_{k+1} (none)
    assert (kp, n_beta) == {"beta": (6, 5), "alpha": (6, 6),
                            "none": (k, k)}[exit_]
    assert bool(new.breakdown) == (exit_ != "none")
    cols_q, cols_p = n_beta + 1, kp
    Q = np.asarray(new.Q, np.float32)
    P = np.asarray(new.P, np.float32)
    assert not Q[:, cols_q:].any() and not P[:, cols_p:].any()
    assert np.abs(Q[:, :cols_q]).max(axis=0).min() > 0.1
    assert np.abs(P[:, :cols_p]).max(axis=0).min() > 0.1

    for other in (ref, graph):
        assert int(other.kprime) == kp
        assert bool(other.breakdown) == bool(new.breakdown)
        for name in ("alphas", "betas"):
            want = np.asarray(getattr(other, name))
            np.testing.assert_allclose(
                np.asarray(getattr(new, name)), want, rtol=0,
                atol=SCALAR_TOL[precision] * np.abs(want).max(),
                err_msg=name)
        for name in ("Q", "P"):
            np.testing.assert_allclose(
                np.asarray(getattr(new, name), np.float32),
                np.asarray(getattr(other, name), np.float32), rtol=0,
                atol=BASIS_TOL[precision], err_msg=name)

    scale = float(np.abs(np.asarray(ref.alphas)).max())
    assert [i for i, _ in new_cb.steps] == [i for i, _ in ref_cb.steps]
    for (_, got), (_, want) in zip(new_cb.steps, ref_cb.steps):
        assert got.keys() == want.keys() == {"alpha", "beta"}
        for name in got:
            assert (abs(got[name] - want[name])
                    <= SCALAR_TOL[precision] * scale)
    if closure:
        # the eager body on a closure runs the same ops as the old loop
        for name in ("alphas", "betas", "Q", "P", "beta1"):
            np.testing.assert_array_equal(
                np.asarray(getattr(new, name)),
                np.asarray(getattr(ref, name)), err_msg=name)


def test_host_steps_compile_once_per_shape(rng):
    """Two estimates at one shape trace the compiled steps on the first
    call only (the first ``Aᵀq``, the left and the right half-step); a new
    shape adds one set; a closure operand runs eagerly and traces none."""
    from repro.api import (SVDSpec, clear_plan_cache, estimate_rank,
                           host_step_traces)
    from repro.core.linop import LinOp
    clear_plan_cache()
    spec = SVDSpec(host_loop=True)
    A = make_lowrank(rng, 90, 60, 6)
    t0 = host_step_traces()
    first = estimate_rank(A, spec, key=jax.random.key(1))
    grew = host_step_traces() - t0
    second = estimate_rank(A, spec, key=jax.random.key(2))
    assert int(first.rank) == int(second.rank) == 6
    assert grew == 3
    assert host_step_traces() - t0 == grew
    B = make_lowrank(rng, 70, 50, 6)
    estimate_rank(B, spec, key=jax.random.key(3))
    assert host_step_traces() - t0 == 2 * grew
    closure = LinOp(A.shape, lambda p: A @ p, lambda q: A.T @ q, A.dtype)
    assert int(estimate_rank(closure, spec, key=jax.random.key(4)).rank) == 6
    assert host_step_traces() - t0 == 2 * grew
