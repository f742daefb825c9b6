"""SolverPlan — compile once, solve many.

``factorize`` is the right call for *one* SVD; the paper's real workloads
(the §V Riemannian similarity loop, rank tracking of a drifting gradient
operator, heavy-traffic serving) issue *thousands* of structurally
identical solves.  Re-resolving the solver, re-wrapping the operand and
re-staging XLA per call is pure overhead, so the plan layer splits the two
phases:

    p = plan(SVDSpec(method="fsvd", rank=8), like=A)   # resolve ONCE
    f1 = p.solve(A,  key=k1)                            # compile ONCE
    f2 = p.solve(A2, key=k2)                            # reuse executable

``plan()`` resolves ``method="auto"`` *operator-aware* (sharded operands →
``fsvd_sharded``, matrix-free sparse/Kronecker/Gram operands → the
streaming blocked solver), pins the solver, and — for in-graph specs —
stages a jitted ``run(op, key, q1) -> (Factorization, ConvergenceInfo)``
with the warm-start buffer donated on accelerator backends.  Compiled
executables are memoized in a process-wide LRU keyed by

    (task, spec, method, operator treedef, leaf shapes/dtypes, arg structure)

where the operator *treedef* carries the static aux data of every pytree
operator — including the ``Mesh`` of a ``ShardedOp`` — so two plans on
different meshes (or mesh factorizations) never share an executable, while
every plan on the same (spec, kind, shape, dtype, mesh) shares one.

Host-loop specs and non-pytree operands (legacy ``LinOp`` closures) fall
back to the eager path transparently: a plan always solves, it just cannot
always stage.
"""
from __future__ import annotations

import collections
import dataclasses
import threading
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.api.callbacks import CaptureCallback, empty_info
from repro.api.registry import get_solver
from repro.api.results import Factorization, RankEstimate
from repro.api.spec import SVDSpec
from repro.core._keys import resolve_key
from repro.core.gk import clear_host_steps
from repro.core.operators import (GramOp, KroneckerOp, LowRankOp, Operator,
                                  ScaledOp, SparseOp, SumOp, TransposedOp,
                                  as_operator, sharding_mesh)
from repro.runtime import faults as _faults
from repro.runtime.spans import span

Array = jax.Array

# methods that run a host-side Python loop (real early exit / restarts)
# and therefore cannot be staged into a single XLA program.
HOST_SIDE_METHODS = frozenset({"fsvd_blocked"})

# built-in in-graph methods the plan may stage + memoize.  Extensions that
# register a jit-safe solver accepting the ``callback`` kwarg opt in here.
_INGRAPH_METHODS = {"fsvd", "rsvd", "fsvd_sharded", "rbk", "gnystrom"}

# sketch-based methods always consume a PRNG key (no warm-start seam).
_NEEDS_KEY = frozenset({"rsvd", "rbk", "gnystrom"})

# "auto" heuristic for *dense* operands: the GK solver tracks the paper's
# accuracy; the sketch is cheaper per pass but its tail triplets degrade
# (paper Fig 1).  A loose tolerance or an explicit power-iteration request
# signals the caller is on the sketch side of the trade-off curve.
_AUTO_SKETCH_TOL = 1e-4


def register_ingraph_method(name: str) -> None:
    """Declare a registered solver stageable by plans (jit-safe, accepts
    ``callback=``)."""
    _INGRAPH_METHODS.add(name)


def method_needs_key(method: str) -> bool:
    """Does ``method`` consume a PRNG key even when warm-started?"""
    return method in _NEEDS_KEY


# ---------------------------------------------------------------------------
# operator-aware method resolution
# ---------------------------------------------------------------------------

def _is_matrix_free(op) -> bool:
    """True when materializing ``op`` densely would defeat its structure —
    these operands want the streaming blocked solver, never the dense
    heuristics (sketch included: an R-SVD range pass is fine, but "auto"
    should not pick it just because ``tol`` is loose)."""
    if isinstance(op, (SparseOp, KroneckerOp, GramOp)):
        return True
    if isinstance(op, TransposedOp):
        return _is_matrix_free(op.inner)
    if isinstance(op, ScaledOp):
        return _is_matrix_free(op.op)
    if isinstance(op, SumOp):
        return any(_is_matrix_free(t) for t in op.terms)
    return False


def resolve_method(spec: SVDSpec, like: Any = None) -> str:
    """Resolve ``method="auto"`` to a registered solver name.

    Operator-aware: an operand flagged ``single_pass_only`` resolves to
    the one solver honouring that contract (``gnystrom``), a *sharded*
    operand resolves to ``fsvd_sharded`` (the shim that enforces the
    in-graph loop), and sparse / Kronecker / Gram operands resolve to the
    streaming ``fsvd_blocked`` — only plain dense (or low-rank /
    legacy-closure) operands consult the tol/power-iters heuristic.
    ``like`` is optional for backward compatibility; without it the dense
    heuristic applies.

    Non-``Operator`` operands are normalized through ``as_operator``
    (which still duck-passes legacy ``LinOp`` closures carrying *both*
    ``mv`` and ``rmv``) — an incidental ``mv`` attribute alone must not
    bypass backend/spec normalization and sharded/matrix-free detection.
    """
    if spec.method != "auto":
        return spec.method
    if like is not None:
        op = like if isinstance(like, Operator) \
            else as_operator(like, backend=spec.backend)
        if getattr(op, "single_pass_only", False):
            return "gnystrom"
        if sharding_mesh(op) is not None:
            return "fsvd_sharded"
        if _is_matrix_free(op):
            return "fsvd_blocked"
    if spec.power_iters > 0 or spec.tol >= _AUTO_SKETCH_TOL:
        return "rsvd"
    return "fsvd"


# ---------------------------------------------------------------------------
# the process-wide compile cache
# ---------------------------------------------------------------------------

_LOCK = threading.RLock()
_CACHE: "collections.OrderedDict[tuple, Any]" = collections.OrderedDict()
_CACHE_SIZE = 128
_STATS = {"traces": 0, "hits": 0, "misses": 0, "evictions": 0}
# single-flight: cache key -> Event, present while one thread builds that
# entry; concurrent requesters wait instead of duplicating the build.
_BUILDING: dict = {}


def clear_plan_cache(reset_stats: bool = False) -> None:
    """Drop every memoized executable (tests / memory pressure), the host
    loop's compiled GK steps included.

    ``reset_stats=True`` also zeroes the hit/miss/eviction/trace counters —
    the serve layer snapshots deltas, but tests (and a server restart)
    want a clean origin."""
    clear_host_steps()
    with _LOCK:
        _CACHE.clear()
        if reset_stats:
            for k in _STATS:
                _STATS[k] = 0


def plan_cache_stats() -> dict:
    """Snapshot of {traces, hits, misses, evictions, entries, hit_rate}.

    ``hits``/``misses`` count :func:`_memoized` lookups (one per staged
    ``solve``/``estimate``/``solve_batched`` call), ``evictions`` counts
    LRU drops, ``traces`` counts real solver tracings — the serve layer's
    bucket-hit-rate metric is ground-truthed against these counters."""
    with _LOCK:
        total = _STATS["hits"] + _STATS["misses"]
        return {**_STATS, "entries": len(_CACHE),
                "hit_rate": _STATS["hits"] / total if total else 0.0}


def trace_count() -> int:
    """Total solver traces staged through plans this process (a retrace
    means a cache key failed to cover something — the compile-once tests
    assert on deltas of this counter)."""
    with _LOCK:
        return _STATS["traces"]


def _bump_traces() -> None:
    with _LOCK:
        _STATS["traces"] += 1


def _operand_signature(op) -> Optional[tuple]:
    """(treedef, ((shape, dtype), ...)) of a pytree operand, or None when
    the operand cannot be staged (non-hashable aux, non-array leaves that
    are not plain scalars)."""
    leaves, treedef = jax.tree_util.tree_flatten(op)
    try:
        hash(treedef)
    except TypeError:
        return None
    sig = []
    for leaf in leaves:
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if (shape is not None and dtype is not None
                and isinstance(leaf, (jax.Array, np.ndarray))):
            sig.append((tuple(shape), str(dtype)))
        elif isinstance(leaf, (bool, int, float, complex)):
            sig.append(((), str(np.result_type(type(leaf)))))
        else:
            return None
    return (treedef, tuple(sig))


def _accepts_callback(fn) -> bool:
    import inspect
    try:
        params = inspect.signature(fn).parameters
    except (TypeError, ValueError):          # builtins / C callables
        return False
    return "callback" in params or any(
        p.kind is inspect.Parameter.VAR_KEYWORD for p in params.values())


def _once_then_parallel(fn):
    """Serialize calls to ``fn`` until the first one completes.

    ``jax.jit`` compiles lazily on the first *call*, and concurrent first
    calls with the same signature can race into duplicate traces.  The
    compile-once contract (exactly one trace per cache key) therefore
    needs the first call fenced; once it returns, the executable exists
    and subsequent calls run lock-free.
    """
    lock = threading.Lock()
    primed = threading.Event()

    def wrapper(*args, **kwargs):
        if primed.is_set():
            return fn(*args, **kwargs)
        with lock:
            out = fn(*args, **kwargs)
            primed.set()
        return out

    return wrapper


def _memoized(cache_key: tuple, build):
    """Single-flight LRU lookup; ``build()`` constructs the jitted callable
    on a miss.

    Concurrent misses on the same key coalesce: the first thread builds
    (off-lock — building may itself take locks, e.g. jax internals) while
    the rest wait on a per-key event, so N threads hammering the same
    (spec, aval) key stage exactly one executable and trace exactly once.
    Waiters count as hits — they end up sharing the built executable.
    """
    while True:
        with _LOCK:
            hit = _CACHE.get(cache_key)
            if hit is not None:
                _CACHE.move_to_end(cache_key)
                _STATS["hits"] += 1
                return hit
            event = _BUILDING.get(cache_key)
            if event is None:
                event = threading.Event()
                _BUILDING[cache_key] = event
                _STATS["misses"] += 1
                builder = True
            else:
                builder = False
        if not builder:
            event.wait()
            continue        # built (or failed — then we take over the build)
        try:
            fn = _once_then_parallel(build())
        except BaseException:
            with _LOCK:
                _BUILDING.pop(cache_key, None)
            event.set()     # wake waiters; one of them retries the build
            raise
        with _LOCK:
            _CACHE[cache_key] = fn
            _CACHE.move_to_end(cache_key)
            while len(_CACHE) > _CACHE_SIZE:
                _CACHE.popitem(last=False)
                _STATS["evictions"] += 1
            _BUILDING.pop(cache_key, None)
        event.set()
        return fn


# ---------------------------------------------------------------------------
# the plan
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class SolverPlan:
    """A resolved (spec, method) pair with a staged-executable cache.

    Build with :func:`plan`.  ``solve`` runs the factorization; in-graph
    specs execute a memoized jitted program (the warm-start buffer ``q1``
    is donated on TPU/GPU), host-loop specs and legacy non-pytree operands
    run eagerly.  The plan itself is stateless — it may be shared freely
    across threads / sessions; all memoization lives in the process-wide
    cache.
    """

    spec: SVDSpec
    method: str
    like: Any = None                 # wrapped template operand (optional)
    donate_q1: bool = True

    # --- introspection ------------------------------------------------
    @property
    def staged(self) -> bool:
        """Can this plan compile (method + loop style allow staging)?"""
        return (self.method in _INGRAPH_METHODS
                and not self.spec.host_loop
                and self.method not in HOST_SIDE_METHODS)

    def operand_key(self, A: Any = None) -> Optional[tuple]:
        """The (treedef, avals) component of the compile-cache key for
        ``A`` — includes every static operator field, e.g. a ShardedOp's
        ``Mesh``; None when the operand cannot be staged."""
        op = self._wrap(A)
        if not isinstance(op, Operator):
            return None
        return _operand_signature(op)

    def _wrap(self, A: Any):
        if A is None:
            if self.like is None:
                raise ValueError(
                    "plan was built without a template operand; pass A to "
                    "solve()/estimate()")
            return self.like
        return as_operator(A, backend=self.spec.backend)

    # --- execution ----------------------------------------------------
    def solve(self, A: Any = None, *, key: Optional[Array] = None,
              q1: Optional[Array] = None, with_info: bool = False,
              callback=None):
        """Run the planned factorization on ``A`` (default: the template
        operand).  Returns a ``Factorization``, or ``(Factorization,
        ConvergenceInfo)`` when ``with_info=True``.  ``callback`` receives
        ``on_info`` either way (and ``on_step`` from host-loop solvers).
        """
        with span("repro.plan.solve"):
            _faults.fire(_faults.PLAN_SOLVE)
            op = self._wrap(A)
            okey = self.operand_key(op) if self.staged else None
            if okey is None:
                return self._solve_eager(op, key, q1, with_info, callback)

            # key resolution happens HERE, per call, so the implicit-key
            # warning keeps firing once per solve (not once per compile)
            # and the staged program only ever sees concrete keys.
            if q1 is None or self.method in _NEEDS_KEY:
                key = resolve_key(key,
                                  caller=f"plan(method={self.method!r})")
            donate = (self.donate_q1 and q1 is not None
                      and jax.default_backend() in ("tpu", "gpu"))
            cache_key = ("solve", self.spec, self.method, okey,
                         key is None, q1 is None, donate)
            fn = _memoized(cache_key, lambda: self._build_solve(donate))
            fact, info = fn(op, key, q1)
            if callback is not None:
                callback.on_info(info)
            return (fact, info) if with_info else fact

    def _build_solve(self, donate: bool):
        solver = get_solver(self.method)
        spec = self.spec
        method = self.method
        takes_cb = _accepts_callback(solver)

        # `run` must close over scalars only — never `self`: the jitted
        # callable lives in the process-wide cache, and a closure over the
        # plan would pin its `like` template operand (a full input array)
        # for the cache entry's lifetime.
        def run(op, key, q1):
            _bump_traces()      # trace-time only: counts real compilations
            cb = CaptureCallback()
            if takes_cb:
                fact = solver(op, spec, key=key, q1=q1, callback=cb)
            else:
                fact = solver(op, spec, key=key, q1=q1)
            info = cb.info if cb.info is not None else empty_info(method)
            return fact, info

        return jax.jit(run, donate_argnums=(2,) if donate else ())

    def _solve_eager(self, op, key, q1, with_info, callback):
        solver = get_solver(self.method)
        rec = CaptureCallback()
        cb: Any = rec
        if callback is not None:
            class _Tee:
                def on_step(self, i, **m):
                    callback.on_step(i, **m)

                def on_info(self, info):
                    rec.on_info(info)
                    callback.on_info(info)
            cb = _Tee()
        if _accepts_callback(solver):
            fact = solver(op, self.spec, key=key, q1=q1, callback=cb)
        else:
            # extension solvers predating the callback protocol
            fact = solver(op, self.spec, key=key, q1=q1)
        info = rec.info if rec.info is not None else empty_info(self.method)
        return (fact, info) if with_info else fact

    def update(self, fact: Factorization, delta: Any, *, beta=1.0):
        """Rank-k update of an existing ``Factorization`` — zero GK
        iterations (see :mod:`repro.core.update`).

        Staged through the same process-wide cache as solves, keyed by the
        (spec, factorization signature, delta signature) triple, so a
        tracking stream pays ONE trace for every update of a given shape.
        ``beta`` enters the staged program as a traced scalar: one
        executable covers all decay factors.
        """
        from repro.core.update import update_factorization
        dop = as_operator(delta, backend=self.spec.backend)
        if not isinstance(dop, LowRankOp):
            raise TypeError(
                f"plan.update requires a low-rank delta (LowRankOp), got "
                f"{type(dop).__name__}; use solve() for unstructured drift")
        backend = self.spec.backend
        fsig = _operand_signature(fact)
        dsig = _operand_signature(dop)
        if fsig is None or dsig is None:
            return update_factorization(fact, dop, beta=beta,
                                        backend=backend)
        cache_key = ("update", self.spec, fsig, dsig)

        def build():
            def run(fact, dop, beta):
                _bump_traces()
                return update_factorization(fact, dop, beta=beta,
                                            backend=backend)
            return jax.jit(run)

        fn = _memoized(cache_key, build)
        return fn(fact, dop, jnp.asarray(beta, jnp.float32))

    # --- sketch-resident seam (repro.sketchres) -----------------------
    def sketch(self, A: Any = None, *, key: Optional[Array] = None,
               budget: Optional[float] = None):
        """ONE staged sweep over the operand → a resident ``SketchState``
        sized by this plan's spec (``sketchres.sketch_operand``).  Keyed
        by the operand signature, so every (re-)sketch of a given operand
        shape shares one executable."""
        from repro.sketchres import BUDGET, sketch_operand
        op = self._wrap(A)
        key = resolve_key(key, caller="plan.sketch")
        budget = BUDGET if budget is None else budget
        okey = _operand_signature(op)
        spec = self.spec
        if okey is None:
            return sketch_operand(op, spec, key=key, budget=budget)
        cache_key = ("sketch", spec, okey, budget)

        def build():
            def run(op, key):
                _bump_traces()
                return sketch_operand(op, spec, key=key, budget=budget)
            return jax.jit(run)

        return _memoized(cache_key, build)(op, key)

    def sketch_fold(self, state, rows, cols, vals):
        """Fold a COO entry batch into a ``SketchState`` through the
        count-sketch scatter-add kernel — staged + memoized per (state
        signature, padded entry count).  Batches are padded to power-of-
        two lengths (``sketchres.pad_entries``; zero-value pads are exact
        no-ops) so an arbitrary delta stream pays O(log E) traces total,
        shared across every tenant with the same panel shapes."""
        from repro.sketchres import apply_entries, pad_entries
        rows, cols, vals = pad_entries(rows, cols, vals)
        ssig = _operand_signature(state)
        if ssig is None:
            return apply_entries(state, rows, cols, vals)
        cache_key = ("sketch_fold", ssig, rows.shape[0])

        def build():
            def run(state, rows, cols, vals):
                _bump_traces()
                return apply_entries(state, rows, cols, vals)
            return jax.jit(run)

        return _memoized(cache_key, build)(state, rows, cols, vals)

    def sketch_fold_delta(self, state, delta):
        """Fold a factored (or dense) drift block into a ``SketchState``
        via two panel products — staged per (state, delta) signature."""
        from repro.sketchres import apply_lowrank_delta
        dop = as_operator(delta, backend=self.spec.backend)
        ssig = _operand_signature(state)
        dsig = _operand_signature(dop)
        if ssig is None or dsig is None:
            return apply_lowrank_delta(state, dop)
        cache_key = ("sketch_fold_delta", ssig, dsig)

        def build():
            def run(state, dop):
                _bump_traces()
                return apply_lowrank_delta(state, dop)
            return jax.jit(run)

        return _memoized(cache_key, build)(state, dop)

    def sketch_reconstruct(self, state):
        """Zero-sweep ``Factorization`` from maintained panels
        (``sketchres.reconstruct`` — stabilized-pinv Nyström core),
        staged per (spec, state signature).  The answer is unverified by
        construction; callers gate it (residual probe + staleness)."""
        from repro.sketchres import reconstruct
        spec = self.spec
        ssig = _operand_signature(state)
        if ssig is None:
            return reconstruct(state, spec)
        cache_key = ("sketch_reconstruct", spec, ssig)

        def build():
            def run(state):
                _bump_traces()
                return reconstruct(state, spec)
            return jax.jit(run)

        return _memoized(cache_key, build)(state)

    def solve_batched(self, ops: Any, *, keys: Optional[Array] = None,
                      q1s: Optional[Array] = None, with_info: bool = False):
        """Run the planned factorization over a *stacked* operand — one
        operator pytree whose array leaves carry a leading batch axis
        (e.g. ``DenseOp(A)`` with ``A`` of shape ``(B, m, n)``).

        This is the serve layer's dispatch seam: the solver is staged
        ONCE per (spec, stacked signature) as ``jit(vmap(run))`` and
        memoized in the same process-wide cache as single solves, so a
        continuous-batching queue pays one trace per (bucket, batch-size)
        and the batched matvecs execute as batched GEMMs.  ``keys`` is a
        stacked key array (one per example; required unless every example
        is warm-started), ``q1s`` an optional stacked warm-start buffer.
        Returns a batched ``Factorization`` (leaves gain the batch axis),
        plus a batched ``ConvergenceInfo`` when ``with_info=True``.

        Unlike ``solve`` there is no eager fallback: batching exists to
        amortize staging, so a plan that cannot stage (host-loop method,
        non-pytree operand) is a caller error.
        """
        _faults.fire(_faults.PLAN_SOLVE)
        if not self.staged:
            raise ValueError(
                f"solve_batched requires a stageable plan; method="
                f"{self.method!r} host_loop={self.spec.host_loop!r} runs "
                "a host-side loop")
        op = as_operator(ops, backend=self.spec.backend)
        okey = _operand_signature(op)
        if okey is None:
            raise ValueError(
                "solve_batched requires a pytree operand with array "
                f"leaves; got {type(ops).__name__}")
        if keys is None and (q1s is None or self.method in _NEEDS_KEY):
            raise ValueError(
                "solve_batched needs stacked `keys` (one per example) "
                "unless every example is warm-started via `q1s`")
        cache_key = ("solve_batched", self.spec, self.method, okey,
                     keys is None, q1s is None)
        fn = _memoized(cache_key,
                       lambda: self._build_batched(keys is None,
                                                   q1s is None))
        fact, info = fn(op, keys, q1s)
        return (fact, info) if with_info else fact

    def _build_batched(self, no_keys: bool, no_q1: bool):
        solver = get_solver(self.method)
        spec = self.spec
        method = self.method
        takes_cb = _accepts_callback(solver)

        # same scalars-only closure rule as _build_solve: the staged
        # callable outlives the plan in the process-wide cache.
        def run(op, key, q1):
            _bump_traces()
            cb = CaptureCallback()
            if takes_cb:
                fact = solver(op, spec, key=key, q1=q1, callback=cb)
            else:
                fact = solver(op, spec, key=key, q1=q1)
            info = cb.info if cb.info is not None else empty_info(method)
            return fact, info

        in_axes = (0, None if no_keys else 0, None if no_q1 else 0)
        return jax.jit(jax.vmap(run, in_axes=in_axes))

    def estimate(self, A: Any = None, *, key: Optional[Array] = None,
                 sigma_tol: Optional[float] = None) -> RankEstimate:
        """Numerical rank (paper Alg 3) under this plan's spec.

        ``spec.host_loop=None`` keeps the per-entry-point default: the
        early-exit host loop (iteration count == rank estimate) — except
        on sharded operands, where the in-graph loop avoids stalling the
        mesh on a host round-trip per step.  In-graph estimates are staged
        through the same compile cache as solves.
        """
        with span("repro.plan.estimate"):
            from repro.core.rank import numerical_rank as _numerical_rank
            spec = self.spec
            if spec.precision is not None:
                # breakdown-based rank detection resolves directions down
                # to the basis storage's CGS2 noise floor — narrowing the
                # storage silently changes what "numerical rank" means, so
                # refuse.
                raise ValueError(
                    "estimate_rank requires full-precision bases; got "
                    f"spec.precision={spec.precision!r} (rank detection "
                    "counts directions the stored basis can certify — use "
                    "precision=None)")
            op = self._wrap(A)
            key = resolve_key(key, caller="estimate_rank")
            if spec.host_loop is None:
                host_loop = sharding_mesh(op) is None
            else:
                host_loop = spec.host_loop

            kwargs = dict(max_iters=spec.max_iters, eps=spec.tol,
                          relative_eps=spec.relative_tol, sigma_tol=sigma_tol,
                          reorth_passes=spec.reorth_passes, dtype=spec.dtype)
            okey = None if host_loop else self.operand_key(op)
            if okey is None:
                res = _numerical_rank(op, key=key, host_loop=host_loop,
                                      **kwargs)
            else:
                cache_key = ("estimate", spec, okey, sigma_tol)

                def build():
                    def run(op, key):
                        _bump_traces()
                        return _numerical_rank(op, key=key, host_loop=False,
                                               **kwargs)
                    return jax.jit(run)

                res = _memoized(cache_key, build)(op, key)
            return RankEstimate(res.rank, res.gk_iterations, res.eigenvalues,
                                method="gk")


def plan(spec: Optional[SVDSpec] = None, *, like: Any = None,
         donate_q1: bool = True, **overrides) -> SolverPlan:
    """Resolve ``spec`` (method, backend, placement) against an optional
    template operand ``like`` and return a reusable :class:`SolverPlan`.

    Keyword overrides merge into the spec exactly as in ``factorize``:
    ``plan(rank=20, like=A)`` == ``plan(SVDSpec(rank=20), like=A)``.
    """
    spec = (spec or SVDSpec())
    if overrides:
        spec = spec.replace(**overrides)
    wrapped = None
    if like is not None:
        wrapped = as_operator(like, backend=spec.backend)
    return SolverPlan(spec=spec, method=resolve_method(spec, wrapped),
                      like=wrapped, donate_q1=donate_q1)
