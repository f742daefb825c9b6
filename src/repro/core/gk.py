"""Algorithm 1 — Golub-Kahan bidiagonalization with reorthogonalization and
breakdown-based numerical-rank detection.

Two execution styles share the same math:

  * ``gk_bidiag``      — in-graph ``lax.fori_loop`` with fixed-size buffers and
                         breakdown *masking* (XLA-static shapes; usable inside
                         jit / grad-compression / the RSGD retraction, and on
                         pod-sharded operators).
  * ``gk_bidiag_host`` — host-side Python loop with *real* early exit (what the
                         paper benchmarks: iteration count == numerical rank).

Both route every half-iteration through the operator's fused
``lanczos_step`` / ``lanczos_rstep`` pipeline (matvec + CGS + norm in one
seam; single-pass Pallas kernels for ``DenseOp(backend="pallas")``), and
both support a mixed-precision mode: ``precision="bf16"`` stores the P/Q
bases half-width in HBM while every reduction/accumulation stays f32.  The
in-graph carry writes one masked *column* per iteration
(``dynamic_update_slice``) instead of re-selecting the whole (m, k+1)
buffer — O(m) instead of O(mk) traffic per step.

Index conventions (paper eq. 9): ``alphas[i] = alpha_{i+1}`` (diagonal of
B_{k+1,k}), ``betas[i] = beta_{i+2}`` (subdiagonal), ``beta1`` is the
normalization of the start vector (not part of B).
"""
from __future__ import annotations

import functools
import threading
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core._keys import resolve_key
from repro.core.linop import LinOp
from repro.core.operators import Operator, as_operator, cgs
from repro.runtime.spans import span

Array = jax.Array

PRECISIONS = (None, "f32", "bf16")


class GKResult(NamedTuple):
    alphas: Array      # (k,)   diag of B_{k+1,k}; zero-masked beyond kprime
    betas: Array       # (k,)   subdiag beta_{2..k+1}; zero-masked beyond kprime
    beta1: Array       # ()     norm of the start vector
    P: Array           # (n, k)   right Lanczos basis, zero cols beyond kprime
    Q: Array           # (m, k+1) left Lanczos basis
    kprime: Array      # ()  int32: number of valid columns (== rank estimate
                       #     when breakdown fired before k iterations)
    breakdown: Array   # ()  bool: did ||q_{k'+1}|| < eps fire?


def _store_dtype(precision, compute_dtype):
    """Basis storage dtype for a ``precision`` knob value.

    ``None`` keeps the compute dtype; ``"f32"`` / ``"bf16"`` pin the basis
    storage width (reductions always accumulate in the compute dtype).
    """
    if precision not in PRECISIONS:
        raise ValueError(
            f"precision must be one of {PRECISIONS}, got {precision!r}")
    if precision is None:
        return compute_dtype
    return jnp.bfloat16 if precision == "bf16" else jnp.float32


def _eff_eps(eps: float, dtype, store) -> float:
    """Breakdown epsilon clamped to the reorthogonalization noise floor.

    The paper uses an absolute eps=1e-8 (float64 NumPy, where the CGS2
    residual floor is ~1e-15).  In float32 the floor is ~40*eps_f32 ~ 5e-6
    relative, so ``relative_eps`` scales by alpha1 (~||A||) AND clamps eps
    to the compute dtype's noise floor — in f64 this preserves the paper's
    1e-8 semantics exactly.  A narrower *storage* dtype raises the floor
    again: CGS2 against a rounded basis bottoms out at ~eps_store² relative
    (one eps_store of overlap survives each pass), so without the clamp a
    bf16 run never detects breakdown and the unprotected three-term
    recurrence amplifies the junk directions until overflow.
    """
    return max(eps, 40.0 * float(jnp.finfo(dtype).eps),
               40.0 * float(jnp.finfo(store).eps) ** 2)


def _notify(callback, alphas, betas, kprime, breakdown):
    """Assemble a ``ConvergenceInfo`` and hand it to ``callback.on_info``.

    The residual proxy per iteration is ``beta_{i+1}`` — the recurrence
    coupling whose collapse under the breakdown threshold is the paper's
    Alg-1 convergence event.  Lazy import: ``repro.api`` imports this
    module at load time, so the reverse edge must stay call-time only.
    """
    if callback is None:
        return
    from repro.api.callbacks import ConvergenceInfo
    callback.on_info(ConvergenceInfo(betas, kprime, breakdown, method="gk"))


def _step(op, p, y, alpha, basis, passes):
    """Dispatch one fused left half-step (LinOp closures lack the method)."""
    fn = getattr(op, "lanczos_step", None)
    if fn is not None:
        return fn(p, y, alpha, basis, passes=passes)
    with span("repro.op.matvec"):
        u = op.mv_fused(p, y, alpha)
    with span("repro.op.cgs"):
        u = cgs(u, basis, passes)
    return u, jnp.linalg.norm(u)


def _rstep(op, q, y, beta, basis, passes):
    fn = getattr(op, "lanczos_rstep", None)
    if fn is not None:
        return fn(q, y, beta, basis, passes=passes)
    with span("repro.op.matvec"):
        v = op.rmv_fused(q, y, beta)
    with span("repro.op.cgs"):
        v = cgs(v, basis, passes)
    return v, jnp.linalg.norm(v)


def _set_col(buf: Array, idx, col: Array, keep) -> Array:
    """Masked write of ``col`` into ``buf[:, idx]`` — O(m) select on the
    column only, never a whole-buffer copy."""
    cur = jax.lax.dynamic_slice_in_dim(buf, idx, 1, axis=1)
    new = jnp.where(keep, col.astype(buf.dtype)[:, None], cur)
    return jax.lax.dynamic_update_slice_in_dim(buf, new, idx, axis=1)


def _set_elt(vec: Array, idx, val, keep) -> Array:
    """Masked write of a scalar into ``vec[idx]``."""
    cur = jax.lax.dynamic_slice(vec, (idx,), (1,))
    new = jnp.where(keep, jnp.asarray(val, vec.dtype)[None], cur)
    return jax.lax.dynamic_update_slice(vec, new, (idx,))


def start_vector(key: jax.Array, m: int, dtype=jnp.float32) -> Array:
    """Paper Alg 1 line 1: q1 ~ N(2, 1)^{m x 1}."""
    return (2.0 + jax.random.normal(key, (m,))).astype(dtype)


def gk_bidiag(
    op: Operator | LinOp | Array,
    k: int,
    *,
    key: Optional[jax.Array] = None,
    q1: Optional[Array] = None,
    eps: float = 1e-8,
    relative_eps: bool = True,
    reorth_passes: int = 2,
    dtype=None,
    precision: Optional[str] = None,
    callback=None,
) -> GKResult:
    """In-graph GK bidiagonalization (fixed k iterations, breakdown masking).

    ``precision="bf16"`` stores the P/Q bases in bfloat16 (half the HBM
    bytes of the bandwidth-bound reorthogonalization streams) while the
    recurrence scalars, carried vectors and all accumulations stay in the
    compute dtype.  The breakdown threshold widens to the storage's CGS2
    noise floor (see :func:`_eff_eps`), so bf16 is a throughput mode for
    fixed-k factorization; rank detection wants full precision.
    """
    op = as_operator(op)
    m, n = op.shape
    if k > min(m, n):
        k = min(m, n)
    if dtype is None:
        dtype = jnp.promote_types(op.dtype, jnp.float32)
    store = _store_dtype(precision, dtype)

    if q1 is None:
        key = resolve_key(key, caller="gk_bidiag")
        q1 = start_vector(key, m, dtype)
    q1 = q1.astype(dtype)

    beta1 = jnp.linalg.norm(q1)
    q = q1 / beta1
    with span("repro.gk.right"):
        with span("repro.op.matvec"):
            p = op.rmv(q).astype(dtype)
        alpha1 = jnp.linalg.norm(p)
        p = p / jnp.where(alpha1 > 0, alpha1, 1.0)

    Q = jnp.zeros((m, k + 1), store).at[:, 0].set(q.astype(store))
    P = jnp.zeros((n, k), store).at[:, 0].set(p.astype(store))
    # sharded operators lay the basis buffers out on their vector sharding
    # up front, so the carried buffers match the fused step's layout
    # instead of being re-sharded on the first iteration.
    place = getattr(op, "place_basis", None)
    if place is not None:
        Q = place(Q, "left")
        P = place(P, "right")
    alphas = jnp.zeros((k,), dtype).at[0].set(alpha1)
    betas = jnp.zeros((k,), dtype)

    eff_eps = _eff_eps(eps, dtype, store)
    thresh = jnp.where(relative_eps, eff_eps * jnp.maximum(alpha1, 1.0), eps)

    class Carry(NamedTuple):
        Q: Array
        P: Array
        alphas: Array
        betas: Array
        q: Array
        p: Array
        kprime: Array
        done: Array

    def body(i, c: Carry):
        # --- left vector: u = A p_i - alpha_i q_i, CGS2, norm (lines 5-7)
        with span("repro.gk.left"):
            u, beta = _step(op, c.p, c.q, c.alphas[i - 1], c.Q,
                            reorth_passes)
            u = u.astype(dtype)
            beta = beta.astype(dtype)
            hit = beta < thresh                                 # line 9
            done = jnp.logical_or(c.done, hit)
            safe_beta = jnp.where(beta > 0, beta, 1.0)
            qn = u / safe_beta                                  # line 8
        # --- right vector: v = A^T q_{i+1} - beta_{i+1} p_i (lines 12-14)
        with span("repro.gk.right"):
            v, alpha = _rstep(op, qn, c.p, beta, c.P, reorth_passes)
            v = v.astype(dtype)
            alpha = alpha.astype(dtype)
            hit_a = alpha < thresh
            done2 = jnp.logical_or(done, hit_a)
            safe_alpha = jnp.where(alpha > 0, alpha, 1.0)
            pn = v / safe_alpha

        keep = jnp.logical_not(done)        # was active at loop entry
        keep2 = jnp.logical_not(done2)
        Qn = _set_col(c.Q, i, qn, keep)
        Pn = _set_col(c.P, i, pn, keep2)
        alphas_n = _set_elt(c.alphas, i, alpha, keep2)
        betas_n = _set_elt(c.betas, i - 1, beta, keep)
        kprime_n = jnp.where(done2, c.kprime, c.kprime + 1)
        return Carry(Qn, Pn, alphas_n, betas_n,
                     jnp.where(keep, qn, c.q), jnp.where(keep2, pn, c.p),
                     kprime_n, done2)

    init = Carry(Q, P, alphas, betas, q, p,
                 jnp.asarray(1, jnp.int32), jnp.asarray(False))
    c = jax.lax.fori_loop(1, k, body, init)

    # final half-iteration (paper lines 5-8 at i=k): beta_{k+1} / q_{k+1}
    # complete B_{k+1,k} — without them the last tridiagonal entry and the
    # identity A P_k = Q_{k+1} B_{k+1,k} are truncated.
    with span("repro.gk.left"):
        u, beta = _step(op, c.p, c.q, c.alphas[c.kprime - 1], c.Q,
                        reorth_passes)
        u = u.astype(dtype)
        beta = beta.astype(dtype)
        valid = jnp.logical_not(c.done) & (beta >= thresh)
        qn = u / jnp.where(beta > 0, beta, 1.0)
    Qf = _set_col(c.Q, c.kprime, qn, valid)
    betas_f = _set_elt(c.betas, c.kprime - 1, beta, valid)
    # in-graph diagnostics: the betas buffer IS the per-iteration residual
    # trace — no extra device work, and under jit the info pytree holds
    # tracers the caller can return as compiled-program outputs.
    _notify(callback, c.alphas, betas_f, c.kprime, c.done)
    return GKResult(c.alphas, betas_f, beta1, c.P, Qf,
                    c.kprime, c.done)


# --- the host loop's steps -------------------------------------------------
#
# Each is one program on a pytree operand: the sweep of A, CGS, the norm, the
# normalization and the masked basis write of a half-step, so the host loop
# enqueues two executables per GK iteration instead of about forty eager
# ops, and XLA folds the transpose of ``Aᵀ q`` into the GEMV instead of
# materializing Aᵀ.  The masks mirror the host's breakdown tests exactly
# (``not x < thresh``, ``thresh`` rounded to the compute dtype), so the
# columns written are those the loop keeps.

_HOST_LOCK = threading.Lock()
_HOST_STEPS = None
_HOST_TRACES = 0


def host_step_traces() -> int:
    """Traces of the host loop's compiled steps this process.

    The steps compile once per operand structure, shape, dtype, ``k`` and
    ``reorth_passes`` (three programs: the first ``Aᵀq``, the left and the
    right half-step; the closing half-step reuses the left one), so the
    count grows on the first call at a shape and never inside a loop —
    a retrace per iteration or per call shows as growth here.
    ``repro.api.clear_plan_cache`` drops the compiled steps with the plans.
    """
    with _HOST_LOCK:
        return _HOST_TRACES


def _host_first(op, q1, *, k, dtype, store):
    """``q = q1 / β₁``, ``p = Aᵀ q / α₁`` and the basis buffers holding
    them in column 0 → ``(q, p, α₁, β₁, Qm, Pm)``."""
    beta1 = jnp.linalg.norm(q1)
    q = q1 / beta1
    with span("repro.gk.right"):
        with span("repro.op.matvec"):
            p = op.rmv(q).astype(dtype)
        alpha1 = jnp.linalg.norm(p)
        p = p / jnp.where(alpha1 > 0, alpha1, 1.0)
    m, n = op.shape
    # fixed-width zero-padded basis buffers: zero columns contribute nothing
    # to CGS (exact), and a constant shape means each step compiles ONCE
    # instead of retracing per appended column.
    Qm = jnp.zeros((m, k + 1), store).at[:, 0].set(q.astype(store))
    Pm = jnp.zeros((n, k), store).at[:, 0].set(p.astype(store))
    place = getattr(op, "place_basis", None)
    if place is not None:
        # sharded operands: the buffers start on the fused step's layout
        Qm = place(Qm, "left")
        Pm = place(Pm, "right")
    return q, p, alpha1, beta1, Qm, Pm


def _host_left(op, p, q, alpha, Qm, j, thresh, *, passes):
    """Left half-step: ``qn = (A p − α q)⊥ / β`` written into ``Qm[:, j]``
    unless ``β < thresh`` → ``(qn, β, Qm)``."""
    with span("repro.gk.left"):
        u, beta = _step(op, p, q, alpha, Qm, passes)
        u = u.astype(q.dtype)
        beta = beta.astype(q.dtype)
        qn = u / jnp.where(beta > 0, beta, 1.0)
    return qn, beta, _set_col(Qm, j, qn, jnp.logical_not(beta < thresh))


def _host_right(op, qn, p, beta, Pm, j, thresh, *, passes):
    """Right half-step: ``pn = (Aᵀ qn − β p)⊥ / α`` written into
    ``Pm[:, j]`` unless ``β`` or ``α`` is under ``thresh`` → ``(pn, α, Pm)``."""
    with span("repro.gk.right"):
        v, alpha = _rstep(op, qn, p, beta, Pm, passes)
        v = v.astype(p.dtype)
        alpha = alpha.astype(p.dtype)
        pn = v / jnp.where(alpha > 0, alpha, 1.0)
    keep = jnp.logical_not((beta < thresh) | (alpha < thresh))
    return pn, alpha, _set_col(Pm, j, pn, keep)


def _counted(fn):
    """A fresh function (so a fresh jit cache) that counts its traces."""
    @functools.wraps(fn)
    def traced(*args, **kwargs):
        global _HOST_TRACES
        with _HOST_LOCK:
            _HOST_TRACES += 1
        return fn(*args, **kwargs)
    return traced


def _compiled_host_steps():
    global _HOST_STEPS
    with _HOST_LOCK:
        if _HOST_STEPS is None:
            _HOST_STEPS = (
                jax.jit(_counted(_host_first),
                        static_argnames=("k", "dtype", "store")),
                # donated buffers: the column writes happen in place
                jax.jit(_counted(_host_left), static_argnames=("passes",),
                        donate_argnames=("Qm",)),
                jax.jit(_counted(_host_right), static_argnames=("passes",),
                        donate_argnames=("Pm",)))
        return _HOST_STEPS


def clear_host_steps() -> None:
    """Drop the compiled host steps; the next call traces them afresh (with
    whatever operator methods are then in place)."""
    global _HOST_STEPS
    with _HOST_LOCK:
        _HOST_STEPS = None


def _is_pytree_operand(op) -> bool:
    """True when ``op`` can be a jit argument: hashable aux data, and only
    arrays or Python scalars as leaves (a ``LinOp`` closure is a leaf)."""
    leaves, treedef = jax.tree_util.tree_flatten(op)
    try:
        hash(treedef)
    except TypeError:
        return False
    return all(isinstance(x, (jax.Array, np.ndarray, bool, int, float))
               for x in leaves)


def gk_bidiag_host(
    op: Operator | LinOp | Array,
    k: int,
    *,
    key: Optional[jax.Array] = None,
    q1: Optional[Array] = None,
    eps: float = 1e-8,
    relative_eps: bool = True,
    reorth_passes: int = 2,
    dtype=None,
    precision: Optional[str] = None,
    callback=None,
) -> GKResult:
    """Host-loop GK with real early exit (paper wall-time behaviour).

    Each GK iteration enqueues two compiled half-steps, each with its
    normalization and masked basis write, then makes ONE device→host sync
    for both recurrence scalars: the right half-step is issued
    speculatively against the device-resident ``β``, and its write is
    masked off when either scalar broke down.  The host keeps only the
    decisions: the breakdown tests, the callback and the scalar lists.

    Pytree operands (every ``Operator``, ``ShardedOp`` included) run the
    steps compiled once per shape, dtype, ``k`` and ``reorth_passes``,
    with the basis buffers donated (see :func:`host_step_traces`).
    Operands that cannot be jit arguments — ``LinOp`` closures, or aux
    data that cannot be hashed — run the same step bodies eagerly.
    """
    op = as_operator(op)
    m, n = op.shape
    if k > min(m, n):
        k = min(m, n)
    if dtype is None:
        dtype = jnp.promote_types(op.dtype, jnp.float32)
    dtype = jax.dtypes.canonicalize_dtype(dtype)
    store = jax.dtypes.canonicalize_dtype(_store_dtype(precision, dtype))

    if q1 is None:
        key = resolve_key(key, caller="gk_bidiag_host")
        q1 = start_vector(key, m, dtype)
    q1 = q1.astype(dtype)

    if _is_pytree_operand(op):
        first, left, right = _compiled_host_steps()
    else:
        first, left, right = _host_first, _host_left, _host_right
    # spans: the dispatch of each step (repro.gk.left / .right) and the
    # device->host reads the loop waits on (repro.gk.sync); the scopes
    # inside the steps tag their device ops.
    with span("repro.gk.right"):
        q, p, alpha_d, beta1, Qm, Pm = first(op, q1, k=k, dtype=dtype,
                                             store=store)
    with span("repro.gk.sync"):
        alpha1 = float(alpha_d)
    thresh = _eff_eps(eps, dtype, store) * max(alpha1, 1.0) \
        if relative_eps else eps
    # rounded to the compute dtype, so the host's tests and the steps'
    # write masks compare the same numbers
    thresh = float(np.asarray(thresh, dtype))
    thresh_d = jnp.asarray(thresh, dtype)
    al = [alpha1]
    be = []
    breakdown = False

    for j in range(1, k):
        with span("repro.gk.left"):
            qn, beta_d, Qm = left(op, p, q, alpha_d, Qm, j, thresh_d,
                                  passes=reorth_passes)
        with span("repro.gk.right"):
            pn, alpha_d, Pm = right(op, qn, p, beta_d, Pm, j, thresh_d,
                                    passes=reorth_passes)
        with span("repro.gk.sync"):
            beta, alpha = (float(x)
                           for x in jax.device_get((beta_d, alpha_d)))
        if callback is not None:
            # the loop just synced these scalars anyway — observing them
            # costs nothing extra.
            callback.on_step(len(al), alpha=alpha, beta=beta)
        if beta < thresh:               # no column written
            breakdown = True
            break
        be.append(beta)
        if alpha < thresh:              # Qm[:, j] written, Pm[:, j] not
            breakdown = True
            break
        al.append(alpha)
        p, q = pn, qn

    if not breakdown and len(al) == k:
        # final half-iteration: beta_{k+1}, q_{k+1} complete B_{k+1,k}
        with span("repro.gk.left"):
            _, beta_d, Qm = left(op, p, q, alpha_d, Qm, k, thresh_d,
                                 passes=reorth_passes)
        with span("repro.gk.sync"):
            beta = float(beta_d)
        if not beta < thresh:
            be.append(beta)

    kp = len(al)
    alphas = np.zeros((k,), dtype)
    alphas[:kp] = al
    betas = np.zeros((k,), dtype)
    betas[:len(be)] = be
    alphas, betas = jnp.asarray(alphas), jnp.asarray(betas)
    _notify(callback, alphas, betas, jnp.asarray(kp, jnp.int32),
            jnp.asarray(breakdown))
    return GKResult(alphas, betas, beta1, Pm, Qm,
                    jnp.asarray(kp, jnp.int32), jnp.asarray(breakdown))
