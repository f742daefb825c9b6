#!/usr/bin/env python3
"""On-chip smoke test: the solve engine, the solve server and the RSL
trainer through their user entry points, on one TPU.

    python chip_smoke.py              # one chip: every phase below
    python chip_smoke.py --chips 4    # four chips: the row-sharded solve only

One chip, in order:

  device   platform, device kind and count; anything but a TPU exits 1.
  dense    paper §6.2 at one-chip scale: A = M·N, M (102400×100),
           N (100×8192), f32, generated on the device.  ``fsvd`` (rank 20,
           200 iterations) on the ``xla`` and ``pallas`` backends, the
           in-graph rank estimate (256 iterations) on both, and ``rbk`` /
           ``gnystrom`` on ``pallas``.  σ are checked against the exact σ
           of the factors, at the per-method tolerance of
           ``tests/test_solver_parity.py``; the estimate must say 100.
  serve    ``SolveServer`` (fsvd, rank 8, pallas) warmed on the default
           shape menu, then anonymous solves and tenant factorize /
           ``delta`` / ``entries`` requests through
           ``repro.launch.solve_serve.run_traffic``: every request ok,
           none failed or degraded, every anonymous σ checked against a
           dense SVD.
  rsl      5 RSGD steps of ``examples/train_rsl.py`` at its defaults
           (10000×10000, rank 5, batch 64, tracking retraction): loss and
           σ finite.

``--chips 4`` runs A = M·N of 98304×81920 f32 (8 GB per chip) row-sharded
on a ``("data",)`` mesh of four chips, ``fsvd_sharded`` (rank 20, 200
iterations) on both backends against the factor reference, and prints the
all-reduce count of each compiled half-step.

Every phase prints one JSON line.  ``first_call_s`` includes compilation;
``second_call_s`` is the same call again.  Both are smoke times, not
benchmark numbers.  The last line is
``{"ok": true, "device": {"platform", "kind", "count"}}``; any failed
check exits non-zero before it.  Data comes from ``--seed``.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

# per-method σ tolerance, max |ŝ − s| / s_max, as in
# tests/test_solver_parity.py SOLVERS
STOL = {"fsvd": 5e-4, "fsvd_sharded": 5e-4, "rbk": 5e-4, "gnystrom": 1e-3}

DENSE_SHAPE, SHARDED_SHAPE, RANK_TRUE = (102400, 8192), (98304, 81920), 100
RANK, ITERS, EST_ITERS = 20, 200, 256


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {msg}")


def timed(fn):
    """(result, first-call s, second-call s) — both calls block on the
    result, the first one includes compilation."""
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    t1 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, t1 - t0, time.perf_counter() - t1


def sigma_err(s, s_true, r: int) -> float:
    import numpy as np
    s = np.asarray(s, np.float64)[:r]
    s_true = np.asarray(s_true, np.float64)
    return float(np.max(np.abs(s - s_true[:r])) / s_true[0])


def factor_sigma(M, N):
    """Exact σ of M·N from thin QRs of the factors (no m×n work)."""
    import jax
    import jax.numpy as jnp
    with jax.default_matmul_precision("highest"):
        rm = jnp.linalg.qr(M, mode="r")
        rn = jnp.linalg.qr(N.T, mode="r")
        return jnp.linalg.svd(rm @ rn.T, compute_uv=False)


def low_rank_operand(key, shape, rank, sharding=None):
    """(M, N, A = M·N) generated on the device; with ``sharding`` (a row
    layout) M and A are laid out row-sharded and N replicated."""
    import jax
    import jax.numpy as jnp
    m, n = shape
    km, kn = jax.random.split(key)
    rep = None
    if sharding is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P
        rep = NamedSharding(sharding.mesh, P())
    M = jax.jit(lambda k: jax.random.normal(k, (m, rank)),
                out_shardings=sharding)(km)
    N = jax.jit(lambda k: jax.random.normal(k, (rank, n)),
                out_shardings=rep)(kn)
    A = jax.jit(lambda M, N: jnp.dot(M, N, precision="highest"),
                out_shardings=sharding)(M, N)
    return M, N, A


def phase_dense(key, shape=DENSE_SHAPE, rank_true=RANK_TRUE, rank=RANK,
                iters=ITERS, est_iters=EST_ITERS) -> None:
    import jax
    from repro.api import SVDSpec, plan

    kA, kS = jax.random.split(key)
    t0 = time.perf_counter()
    M, N, A = low_rank_operand(kA, shape, rank_true)
    s_true = jax.block_until_ready(factor_sigma(M, N))
    emit("dense/operand", shape=list(shape), rank=rank_true,
         gbytes=A.nbytes / 1e9, setup_s=time.perf_counter() - t0,
         sigma_max=float(s_true[0]), sigma_min=float(s_true[-1]))

    def solve(method, backend, **kw):
        p = plan(SVDSpec(method=method, rank=rank, backend=backend, **kw),
                 like=A)
        fact, first, second = timed(lambda: p.solve(A, key=kS))
        err = sigma_err(fact.s, s_true, rank)
        emit(f"dense/{method}", backend=backend, rank=rank, **kw,
             sigma_rel_err=err, tol=STOL[method], first_call_s=first,
             second_call_s=second)
        check(err < STOL[method],
              f"{method}[{backend}] σ error {err:.3e} ≥ {STOL[method]}")

    for backend in ("xla", "pallas"):
        solve("fsvd", backend, max_iters=iters)
        p = plan(SVDSpec(max_iters=est_iters, backend=backend,
                         host_loop=False), like=A)
        est, first, second = timed(lambda: p.estimate(A, key=kS))
        emit("dense/estimate", backend=backend, max_iters=est_iters,
             rank=int(est.rank), expected=rank_true, first_call_s=first,
             second_call_s=second)
        check(int(est.rank) == rank_true,
              f"estimate[{backend}] rank {int(est.rank)} != {rank_true}")
    # rbk's basis (30 × 5 = 150 columns) and gnystrom's panel (128)
    # cover the rank-100 range, so both are exact up to roundoff.
    solve("rbk", "pallas", passes=4)
    solve("gnystrom", "pallas", sketch_dim=128)


def phase_serve(seed: int, n_anon: int = 40, n_tenant: int = 24,
                shapes=None) -> None:
    import jax
    import numpy as np
    from repro.api import SVDSpec
    from repro.launch.solve_serve import run_traffic
    from repro.serve import SolveServer
    from repro.serve.traffic import DEFAULT_SHAPES, synthetic_stream

    shapes = DEFAULT_SHAPES if shapes is None else shapes
    spec = SVDSpec(method="fsvd", rank=8, backend="pallas")
    server = SolveServer(spec, key=jax.random.key(seed),
                         hang_timeout_s=300.0)
    t0 = time.perf_counter()
    staged = server.warmup(shapes)
    warm_s = time.perf_counter() - t0

    anon = list(synthetic_stream(n_anon, shapes=shapes, rank=8, seed=seed))
    tenant = list(synthetic_stream(n_tenant, shapes=shapes, rank=8,
                                   tenants=2, tenant_fraction=1.0,
                                   structured_drift=True, seed=seed + 1))
    for req in synthetic_stream(n_tenant, shapes=shapes, rank=8, tenants=2,
                                tenant_fraction=1.0, entry_drift_nnz=64,
                                seed=seed + 2):
        req.tenant = "entries-" + req.tenant
        tenant.append(req)
    kinds = {}
    for req in tenant:
        kinds[req.kind] = kinds.get(req.kind, 0) + 1
    check(kinds.get("delta", 0) > 0 and kinds.get("entries", 0) > 0,
          f"tenant stream lacks delta/entries requests: {kinds}")

    answers = []

    def keep(req, outcome, detail):
        if outcome == "ok":
            answers.append((req.A, detail.value.s))

    with server:
        # anonymous solves from 4 clients (continuous batching); tenant
        # streams from one client, so each tenant's drifts arrive after
        # its first factorize, in order.
        c_anon = run_traffic(server, anon, clients=4, on_result=keep)
        c_ten = run_traffic(server, tenant, clients=1)
        stats = server.stats()
    errs = []
    for A, s in answers:
        with jax.default_matmul_precision("highest"):
            s_true = np.asarray(jax.numpy.linalg.svd(A, compute_uv=False))
        errs.append(sigma_err(s, s_true, 8))
    total = len(anon) + len(tenant)
    ok = c_anon["ok"] + c_ten["ok"]
    failed = c_anon["failed"] + c_ten["failed"]
    degraded = c_anon["degraded"] + c_ten["degraded"]
    rejected = stats["health"]["degraded_rejected"]
    emit("serve", requests=total, tenant_kinds=kinds, ok=ok, failed=failed,
         degraded=degraded, degraded_rejected=rejected,
         errors={**c_anon["errors"], **c_ten["errors"]},
         warmup_signatures=staged, warmup_s=warm_s,
         anon_wall_s=c_anon["wall_s"], tenant_wall_s=c_ten["wall_s"],
         anon_sigma_max_rel_err=max(errs), tol=STOL["fsvd"])
    check(ok == total and failed == 0 and degraded == 0 and rejected == 0,
          f"serve: ok {ok}/{total}, failed {failed}, degraded {degraded}, "
          f"degraded_rejected {rejected}")
    check(len(errs) == len(anon) and max(errs) < STOL["fsvd"],
          f"serve: anonymous σ error {max(errs):.3e} ≥ {STOL['fsvd']}")


def phase_rsl(argv=("--steps", "5")) -> None:
    import numpy as np
    path = ROOT / "examples" / "train_rsl.py"
    mod_spec = importlib.util.spec_from_file_location("train_rsl", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    out = mod.main(list(argv))
    s = np.asarray(out["W"].s)
    emit("rsl", steps=len(out["losses"]), losses=out["losses"],
         sigma=s.tolist(), first_call_s=out["compile_s"],
         step_s=out["step_s"])
    check(len(out["losses"]) > 0 and np.all(np.isfinite(out["losses"]))
          and np.all(np.isfinite(s)), "rsl: non-finite loss or σ")


def allreduce_count(fn, *args) -> int:
    import jax
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    return sum(line.count(" all-reduce(") + line.count(" all-reduce-start(")
               for line in hlo.splitlines())


def phase_sharded(key, devices, shape=SHARDED_SHAPE, rank_true=RANK_TRUE,
                  rank=RANK, iters=ITERS) -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding
    import repro.distributed.gk_dist  # noqa: F401  (registers fsvd_sharded)
    from repro.api import SVDSpec, plan
    from repro.distributed.matvec import sharded_operator
    from repro.distributed.partition import operator_spec
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((len(devices),), ("data",), devices=devices)
    rows = NamedSharding(mesh, operator_spec(mesh))
    kA, kS = jax.random.split(key)
    t0 = time.perf_counter()
    M, N, A = low_rank_operand(kA, shape, rank_true, sharding=rows)
    s_true = jax.block_until_ready(
        factor_sigma(jax.device_put(M, devices[0]),
                     jax.device_put(N, devices[0])))
    emit("sharded/operand", shape=list(shape), rank=rank_true,
         devices=len(devices), gbytes=A.nbytes / 1e9,
         gbytes_per_chip=A.nbytes / 1e9 / len(devices),
         setup_s=time.perf_counter() - t0)

    m, n = shape
    for backend in ("xla", "pallas"):
        op = sharded_operator(A, mesh, backend=backend)
        p = plan(SVDSpec(method="fsvd_sharded", rank=rank, max_iters=iters,
                         backend=backend), like=op)
        fact, first, second = timed(lambda: p.solve(op, key=kS))
        err = sigma_err(fact.s, s_true, rank)
        Q = jnp.zeros((m, iters + 1), jnp.float32)
        Pb = jnp.zeros((n, iters), jnp.float32)
        left = allreduce_count(
            lambda o, p_, y, Q: o.lanczos_step(p_, y, 0.5, Q), op,
            jnp.zeros((n,)), jnp.zeros((m,)), Q)
        right = allreduce_count(
            lambda o, q, y, P_: o.lanczos_rstep(q, y, 0.5, P_), op,
            jnp.zeros((m,)), jnp.zeros((n,)), Pb)
        emit("sharded/fsvd_sharded", backend=backend, rank=rank,
             max_iters=iters, sigma_rel_err=err, tol=STOL["fsvd_sharded"],
             allreduce_left_half_step=left, allreduce_right_half_step=right,
             first_call_s=first, second_call_s=second)
        check(err < STOL["fsvd_sharded"],
              f"fsvd_sharded[{backend}] σ error {err:.3e}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 = the row-sharded fsvd_sharded phase on a "
                         "four-chip mesh, and nothing else")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    check((ROOT / "src" / "repro").is_dir(),
          f"no repro package under {ROOT / 'src'}; run from a checkout")
    sys.path.insert(0, str(ROOT / "src"))

    import jax
    devices = jax.devices()
    dev = devices[0]
    emit("device", platform=dev.platform, kind=dev.device_kind,
         count=len(devices))
    check(dev.platform == "tpu", f"platform is {dev.platform!r}, not tpu")
    check(len(devices) >= args.chips,
          f"--chips {args.chips} needs {args.chips} devices; "
          f"{len(devices)} present")

    from repro.launch.cache import enable_compile_cache
    emit("compile_cache", dir=enable_compile_cache())
    key = jax.random.PRNGKey(args.seed)
    if args.chips == 4:
        devices = devices[:4]
        phase_sharded(key, devices)
    else:
        phase_dense(key)
        phase_serve(args.seed)
        phase_rsl()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
