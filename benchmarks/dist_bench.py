"""Sharded-solver scaling sweep: the perf trajectory grows a device axis.

For each device count d in {1, 2, 4, 8} that the process has (in one
process, over a ``("data",)`` mesh of the first d of ``jax.devices()``),
lays a dense operand out row-sharded and times

  * one fused ``lanczos_step`` / ``lanczos_rstep`` (the one-psum-per-half-
    step seam — the unit of communication at scale), and
  * a full in-graph ``method="fsvd_sharded"`` solve,

all jitted, via the shared ``benchmarks.common.timeit``.  A device count
larger than the devices present is an error, not a skip.  On CPU the
devices come from ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
set before JAX starts; those shards share one CPU, so wall-clock does not
improve with the device count there.  Each record carries a ``devices``
field, and ``benchmarks.reanalyze`` re-derives the ``*_vs_1dev`` ratios
from the raw timings.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 PYTHONPATH=src \
        python -m benchmarks.run --only dist --emit-json BENCH_pr4.json

Section schema ``dist/v1``: ``{"schema", "backend", "interpret", "passes",
"records": [{"devices", "m", "n", "k", "rank", "step_ms", "rstep_ms",
"solve_ms", "step_vs_1dev", "solve_vs_1dev"}]}``.
"""
from __future__ import annotations

SIZES = [(4096, 1024, 64)]
QUICK_SIZES = [(512, 256, 16)]
DEVICE_COUNTS = (1, 2, 4, 8)
PASSES = 2
RANK = 8


def _sweep(devices: int, sizes, repeats: int) -> list:
    """Time the fused seam and a solve on a mesh of ``devices`` devices."""
    import jax
    import jax.numpy as jnp

    from benchmarks.common import timeit
    from repro.api import SVDSpec, factorize_jit
    from repro.distributed.matvec import sharded_operator
    from repro.launch.mesh import make_mesh
    import repro.distributed.gk_dist  # noqa: F401  (registers fsvd_sharded)

    have = jax.device_count()
    if devices > have:
        raise RuntimeError(
            f"dist_bench needs {devices} devices; {have} present")
    mesh = make_mesh((devices,), ("data",),
                     devices=jax.devices()[:devices])
    records = []
    for m, n, k in sizes:
        ks = jax.random.split(jax.random.PRNGKey(m + n + k), 5)
        A = jax.random.normal(ks[0], (m, n))
        op = sharded_operator(A, mesh)
        p = jax.random.normal(ks[1], (n,))
        q = jax.random.normal(ks[2], (m,))
        Q = jnp.linalg.qr(jax.random.normal(ks[3], (m, k)))[0]
        Pb = jnp.linalg.qr(jax.random.normal(ks[4], (n, k)))[0]

        step = jax.jit(lambda p, q, Q: op.lanczos_step(p, q, 0.4, Q,
                                                       passes=PASSES))
        rstep = jax.jit(lambda q, p, Pb: op.lanczos_rstep(q, p, 0.2, Pb,
                                                          passes=PASSES))
        ts, _ = timeit(step, p, q, Q, repeats=repeats)
        tr, _ = timeit(rstep, q, p, Pb, repeats=repeats)

        # factorize_jit: one compiled executable, so the timing is solve
        # execution (matvecs + psums), not per-call facade tracing.
        spec = SVDSpec(method="fsvd_sharded", rank=RANK,
                       max_iters=min(4 * RANK, k))
        solve = factorize_jit(spec, donate_q1=False)
        tsolve, _ = timeit(solve, op, jax.random.PRNGKey(0), None,
                           repeats=max(repeats - 1, 1))
        records.append({"devices": devices, "m": m, "n": n, "k": k,
                        "rank": RANK, "step_ms": ts * 1e3,
                        "rstep_ms": tr * 1e3, "solve_ms": tsolve * 1e3})
    return records


def run(sizes=None, devices=None, repeats: int = 3,
        quick: bool = False) -> dict:
    """Sweep device counts in this process and aggregate.  ``devices``
    defaults to the counts in ``DEVICE_COUNTS`` that the process has; an
    explicit count above ``jax.device_count()`` raises."""
    import jax

    from benchmarks.common import fmt_table

    sizes = sizes if sizes is not None else (QUICK_SIZES if quick else SIZES)
    if devices is None:
        devices = [d for d in DEVICE_COUNTS if d <= jax.device_count()]
    records = []
    for d in devices:
        records.extend(_sweep(d, sizes, repeats))
    backend = jax.default_backend()

    base = {(r["m"], r["n"], r["k"]): r for r in records
            if r["devices"] == 1}
    rows = []
    for r in records:
        b = base.get((r["m"], r["n"], r["k"]))
        r["step_vs_1dev"] = b["step_ms"] / r["step_ms"] if b else None
        r["solve_vs_1dev"] = b["solve_ms"] / r["solve_ms"] if b else None
        rows.append([f"{r['m']}x{r['n']} k={r['k']}", r["devices"],
                     f"{r['step_ms']:.2f}", f"{r['rstep_ms']:.2f}",
                     f"{r['solve_ms']:.1f}",
                     f"{r['step_vs_1dev']:.2f}x" if b else "-",
                     f"{r['solve_vs_1dev']:.2f}x" if b else "-"])
    print(f"\n## Sharded solver scaling ({backend}; on forced host devices "
          "the ratios are rendezvous-overhead probes)")
    print(fmt_table(["shape", "devices", "step ms", "rstep ms", "solve ms",
                     "step vs 1dev", "solve vs 1dev"], rows))
    return {"schema": "dist/v1", "backend": backend,
            "interpret": backend != "tpu", "passes": PASSES,
            "records": records}


if __name__ == "__main__":
    import sys

    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    run(quick="--quick" in sys.argv)
