"""repro.api — the spec-driven solver facade (the public entry point).

Three layers since PR 5:

    from repro.api import SVDSpec, factorize, plan, session

    fact = factorize(A, SVDSpec(method="fsvd", rank=20), key=key)  # one-shot
    p = plan(SVDSpec(rank=20), like=A); p.solve(A, key=key)        # compile
                                                                   # once,
                                                                   # solve many
    sess = session(A, rank=20, key=key)                            # track a
    sess.solve(); sess.update(A_drifted); sess.history             # drifting
                                                                   # operator

``plan`` resolves method/backend/placement once and memoizes compiled
solvers process-wide (the cache key includes the operand kind, shape,
dtype and mesh); ``session`` adds warm-started tracking with a
restart-vs-refine decision from the subspace angle, residual history via
the ``ConvergenceInfo`` diagnostics, and checkpointable state.

Everything — dense arrays, implicit low-rank operators (``LowRankOp``),
operator algebra (``A.T``, ``A + B``, ``alpha * A``), pod-sharded operators
(``repro.distributed.ShardedOp``) — goes through the same calls; the
solver registry (``register_solver``) lets extensions plug in new methods.

The legacy per-solver entry points (``repro.core.fsvd/rsvd/numerical_rank``)
remain as deprecated shims.
"""
from repro.api.facade import (estimate_rank, factorize, factorize_jit,
                              resolve_method)
from repro.api.callbacks import (CaptureCallback, ConvergenceCallback,
                                 ConvergenceInfo, RecordingCallback)
from repro.api.plan import (SolverPlan, clear_plan_cache, plan,
                            plan_cache_stats, register_ingraph_method,
                            trace_count)
from repro.api.registry import (available_solvers, get_solver,
                                register_solver)
from repro.api.results import Factorization, RankEstimate
from repro.api.session import Session, session
from repro.api.spec import METHODS, SVDSpec
from repro.core._keys import ImplicitKeyWarning, resolve_key
from repro.core.gk import host_step_traces
from repro.core.operators import (DenseOp, GramOp, KroneckerOp, LowRankOp,
                                  Operator, ScaledOp, SinglePassOp,
                                  SparseOp, SumOp, TransposedOp,
                                  as_operator)
from repro.core.update import (downdate_cols, downdate_rows,
                               update_factorization)

# importing the module registers the built-in solvers
from repro.api import solvers as _solvers  # noqa: E402,F401  (side effect)

_resolve_key = resolve_key   # the facade's canonical key helper

__all__ = [
    "SVDSpec", "METHODS", "factorize", "factorize_jit", "estimate_rank",
    "resolve_method",
    "plan", "SolverPlan", "clear_plan_cache", "plan_cache_stats",
    "trace_count", "host_step_traces", "register_ingraph_method",
    "session", "Session",
    "update_factorization", "downdate_rows", "downdate_cols",
    "ConvergenceInfo", "ConvergenceCallback", "RecordingCallback",
    "CaptureCallback",
    "Factorization", "RankEstimate",
    "register_solver", "get_solver", "available_solvers",
    "Operator", "DenseOp", "LowRankOp", "SumOp", "ScaledOp",
    "TransposedOp", "SparseOp", "KroneckerOp", "GramOp", "SinglePassOp",
    "as_operator",
    "resolve_key", "ImplicitKeyWarning",
]
