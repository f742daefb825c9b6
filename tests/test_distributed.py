"""Distributed-layer tests on 8 fake devices.

Each test runs in a SUBPROCESS with ``--xla_force_host_platform_device_count=8``
so the main pytest process keeps its single real device (the dry-run rule).
"""
import pytest

from conftest import run_on_devices


def run_sub(body: str) -> dict:
    """Run ``body`` in a fresh 8-device python; it must print a JSON dict."""
    return run_on_devices(body, 8)


def test_sharded_matvec_matches_dense():
    res = run_sub("""
        from repro.launch.mesh import make_mesh
        from repro.distributed.matvec import place_operator, sharded_operator
        mesh = make_mesh((4, 2), ("data", "model"))
        A = jax.random.normal(jax.random.PRNGKey(0), (64, 32))
        Ad = place_operator(A, mesh)
        op = sharded_operator(Ad, mesh)
        p = jax.random.normal(jax.random.PRNGKey(1), (32,))
        q = jax.random.normal(jax.random.PRNGKey(2), (64,))
        e1 = float(jnp.max(jnp.abs(op.mv(p) - A @ p)))
        e2 = float(jnp.max(jnp.abs(op.rmv(q) - A.T @ q)))
        e3 = float(jnp.max(jnp.abs(op.mv_fused(p, q, 0.5) - (A @ p - 0.5*q))))
        print(json.dumps({"e1": e1, "e2": e2, "e3": e3}))
    """)
    assert res["e1"] < 1e-4 and res["e2"] < 1e-4 and res["e3"] < 1e-4


def test_distributed_fsvd_matches_dense():
    res = run_sub("""
        from repro.launch.mesh import make_mesh
        from repro.distributed.gk_dist import fsvd_sharded, rank_sharded
        from repro.core import fsvd
        mesh = make_mesh((4, 2), ("data", "model"))
        M = jax.random.normal(jax.random.PRNGKey(0), (256, 64))
        N = jax.random.normal(jax.random.PRNGKey(1), (64, 128))
        A = M @ N
        out = fsvd_sharded(A, mesh, 8, 40)
        s_true = jnp.linalg.svd(A, compute_uv=False)[:8]
        err = float(jnp.max(jnp.abs(out.s - s_true) / s_true))
        rk = rank_sharded(A, mesh, max_iters=100)
        print(json.dumps({"err": err, "rank": int(rk.rank)}))
    """)
    assert res["err"] < 1e-3
    assert res["rank"] == 64


def test_multipod_mesh_axes():
    res = run_sub("""
        from repro.launch.mesh import make_mesh
        from repro.distributed.matvec import place_operator, sharded_operator
        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        A = jax.random.normal(jax.random.PRNGKey(0), (64, 32))
        op = sharded_operator(place_operator(A, mesh), mesh)
        p = jax.random.normal(jax.random.PRNGKey(1), (32,))
        err = float(jnp.max(jnp.abs(op.mv(p) - A @ p)))
        print(json.dumps({"err": err}))
    """)
    assert res["err"] < 1e-4


def test_compressed_mean_grads():
    res = run_sub("""
        from repro.launch.mesh import make_mesh
        from repro.distributed import compression as C
        from repro.configs.base import FsvdConfig
        cfg = FsvdConfig(compression_rank=8, compression_min_dim=32,
                         max_iters=24)
        mesh = make_mesh((8,), ("data",))
        lowU = jax.random.normal(jax.random.PRNGKey(3), (128, 8))
        lowV = jax.random.normal(jax.random.PRNGKey(4), (8, 96))
        G = 0.01 * jax.random.normal(jax.random.PRNGKey(2), (8, 128, 96)) \\
            + (lowU @ lowV)[None]
        small = jnp.broadcast_to(jnp.arange(8.0)[:, None], (8, 8))

        def body(g, sm, e):
            grads = {"w": g[0], "tiny": sm[0]}
            ef = {"w": e[0], "tiny": jnp.zeros(())}
            mean, new_ef, stats = C.compressed_mean_grads(grads, ef, "data",
                                                          cfg)
            return (mean["w"][None], mean["tiny"][None],
                    new_ef["w"][None],
                    jnp.stack([stats.dense_bytes, stats.compressed_bytes])[None])

        out = jax.jit(jax.shard_map(body, mesh=mesh,
            in_specs=(P("data"), P("data"), P("data")),
            out_specs=(P("data"), P("data"), P("data"), P("data")),
            check_vma=False))(G, small, jnp.zeros((8, 128, 96)))
        mean_true = G.mean(0)
        rel = float(jnp.linalg.norm(out[0][0] - mean_true)
                    / jnp.linalg.norm(mean_true))
        tiny_err = float(jnp.max(jnp.abs(out[1][0] - 3.5)))
        ef_norm = float(jnp.linalg.norm(out[2][0]))
        print(json.dumps({"rel": rel, "tiny": tiny_err, "ef": ef_norm}))
    """)
    assert res["rel"] < 5e-3          # low-rank-dominated mean well captured
    assert res["tiny"] < 1e-6         # small leaves use plain psum-mean
    assert res["ef"] > 0              # residual captured for error feedback


def test_ef_accumulates_what_compression_drops():
    """DP-SGD with EF compression tracks uncompressed SGD on a quadratic.

    The entire optimization runs inside ONE jitted shard_map + fori_loop —
    a single executable keeps the CPU-collective rendezvous count low (the
    many-small-executions pattern is flaky on the host backend)."""
    res = run_sub("""
        from repro.launch.mesh import make_mesh
        from repro.distributed import compression as C
        from repro.configs.base import FsvdConfig
        cfg = FsvdConfig(compression_rank=2, compression_min_dim=8,
                         max_iters=6)
        mesh = make_mesh((8,), ("data",))
        key = jax.random.PRNGKey(0)
        Wstar = jax.random.normal(key, (32, 24))
        X = jax.random.normal(jax.random.fold_in(key, 1), (8, 16, 32))
        lr, steps = 0.1, 150

        def run(x):
            x = x[0]                       # (16, 32) local shard

            def one(i, carry):
                W, e = carry
                r = x @ (W - Wstar)
                g = x.T @ r / x.shape[0]
                mean, new_e, _ = C.compressed_mean_grads(
                    {"w": g}, {"w": e}, "data", cfg)
                return W - lr * mean["w"], new_e["w"]

            W, _ = jax.lax.fori_loop(
                0, steps, one, (jnp.zeros((32, 24)), jnp.zeros((32, 24))))
            return W[None]

        W = jax.jit(jax.shard_map(run, mesh=mesh, in_specs=(P("data"),),
                                  out_specs=P("data"),
                                  check_vma=False))(X)[0]
        err = float(jnp.linalg.norm(W - Wstar) / jnp.linalg.norm(Wstar))
        print(json.dumps({"err": err}))
    """)
    assert res["err"] < 0.15   # converges to the optimum despite rank-2 comm


def test_partition_rules_divisibility_fallback():
    res = run_sub("""
        from repro.launch.mesh import make_mesh
        from repro.distributed.partition import logical_to_spec
        mesh = make_mesh((2, 4), ("data", "model"))
        s1 = logical_to_spec(("embed", "heads", "head_dim"), (64, 8, 32), mesh)
        s2 = logical_to_spec(("embed", "kv_heads", "head_dim"), (64, 3, 32),
                             mesh)   # 3 % 4 != 0 -> replicated
        s3 = logical_to_spec(("experts", "embed", "mlp"), (8, 64, 128), mesh)
        print(json.dumps({"s1": str(s1), "s2": str(s2), "s3": str(s3)}))
    """)
    assert "'model'" in res["s1"]
    assert "'model'" not in res["s2"]
    # conflict rule: experts claim model; mlp must NOT re-claim it
    assert res["s3"].count("'model'") == 1 and "'data'" in res["s3"]


def test_fused_step_is_one_collective_per_half_step():
    """Acceptance: on a row-sharded mesh each fused GK half-step issues
    exactly ONE psum (asserted on the jaxpr) lowering to exactly ONE
    all-reduce (asserted on the compiled HLO).  A "model" axis adds the
    matvec-reduce collective — exactly one more, never one per dot."""
    res = run_sub("""
        from repro.launch.mesh import make_mesh
        from repro.distributed.matvec import sharded_operator
        from repro.launch import hlo_analysis

        def iter_eqns(jaxpr):
            for eqn in jaxpr.eqns:
                yield eqn
                for v in eqn.params.values():
                    vs = v if isinstance(v, (tuple, list)) else [v]
                    for x in vs:
                        if hasattr(x, "eqns"):
                            yield from iter_eqns(x)
                        elif hasattr(x, "jaxpr"):
                            yield from iter_eqns(x.jaxpr)

        def psums(fn, *args):
            jx = jax.make_jaxpr(fn)(*args)
            return sum(1 for e in iter_eqns(jx.jaxpr)
                       if e.primitive.name == "psum")

        A = jax.random.normal(jax.random.PRNGKey(0), (128, 64))
        p = jax.random.normal(jax.random.PRNGKey(1), (64,))
        q = jax.random.normal(jax.random.PRNGKey(2), (128,))
        Q = jnp.linalg.qr(jax.random.normal(jax.random.PRNGKey(3),
                                            (128, 9)))[0]
        Pb = jnp.linalg.qr(jax.random.normal(jax.random.PRNGKey(4),
                                             (64, 9)))[0]
        out = {}
        for tag, shape, axes, backend in [
                ("rows", (8,), ("data",), "xla"),
                ("rows_pallas", (8,), ("data",), "pallas"),
                ("pods", (2, 4), ("pod", "data"), "xla"),
                ("model", (4, 2), ("data", "model"), "xla")]:
            op = sharded_operator(A, make_mesh(shape, axes), backend=backend)
            out[tag] = [
                psums(lambda p, q, Q: op.lanczos_step(p, q, 0.4, Q)[0],
                      p, q, Q),
                psums(lambda q, p, Pb: op.lanczos_rstep(q, p, 0.2, Pb)[0],
                      q, p, Pb)]
        op = sharded_operator(A, make_mesh((8,), ("data",)))
        hlo = jax.jit(lambda p, q, Q: op.lanczos_step(p, q, 0.4, Q)) \\
            .lower(p, q, Q).compile().as_text()
        counts = hlo_analysis.analyze(hlo).collective_counts
        out["hlo"] = {k: v for k, v in counts.items() if v}
        print(json.dumps(out))
    """)
    assert res["rows"] == [1, 1], res
    assert res["rows_pallas"] == [1, 1], res
    assert res["pods"] == [1, 1], res
    assert res["model"] == [2, 2], res          # +1 matvec-reduce, not +per-dot
    assert res["hlo"] == {"all-reduce": 1}, res


def test_sharded_solvers_match_dense_on_8_devices():
    """Acceptance: sharded fsvd / fsvd_blocked / rsvd match their
    single-device factorizations to 1e-5 (f32) on a non-divisible shape."""
    res = run_sub("""
        from repro.api import SVDSpec, factorize
        from repro.launch.mesh import make_mesh
        from repro.distributed.matvec import sharded_operator
        import repro.distributed.gk_dist  # registers fsvd_sharded
        mesh = make_mesh((8,), ("data",))
        # the 1e3 scale makes sigma_max(A) ~ 3e4: regression cover for the
        # distributed orthonormalization's drop threshold, which must be
        # scale-relative (a fixed scale silently dropped all expansion
        # columns for sigma_max > ~2.5e3 and degraded fsvd_blocked)
        M = jax.random.normal(jax.random.PRNGKey(0), (100, 12))
        A = 1e3 * (M @ jax.random.normal(jax.random.PRNGKey(1), (12, 70))
                   + 1e-4 * jax.random.normal(jax.random.PRNGKey(2),
                                              (100, 70)))
        smax = float(jnp.linalg.svd(A, compute_uv=False)[0])
        key = jax.random.PRNGKey(7)
        out = {}
        for method, kw in [("fsvd_sharded", dict(max_iters=48)),
                           ("fsvd_blocked", dict()),
                           ("rsvd", dict(power_iters=3, oversample=10))]:
            spec = SVDSpec(method=method, rank=8, **kw)
            sharded = factorize(sharded_operator(A, mesh), spec, key=key)
            if method == "fsvd_sharded":
                ref = factorize(sharded_operator(A, make_mesh((1,),
                                                              ("data",))),
                                spec, key=key)
            else:
                ref = factorize(A, spec, key=key)
            out[method] = float(np.max(np.abs(np.asarray(sharded.s)
                                              - np.asarray(ref.s))) / smax)
        print(json.dumps(out))
    """)
    for method, err in res.items():
        assert err < 1e-5, f"{method}: sharded vs single σ error {err:.2e}"


def test_sharded_sparse_and_gram_operands():
    """ShardedOp wraps SparseOp (row-partitioned ELL packs) and GramOp;
    estimate_rank + fsvd_blocked accept both without densifying."""
    res = run_sub("""
        from repro.api import SVDSpec, estimate_rank, factorize
        from repro.core.operators import DenseOp, GramOp, SparseOp
        from repro.launch.mesh import make_mesh
        from repro.distributed.matvec import sharded_operator
        mesh = make_mesh((8,), ("data",))
        key = jax.random.PRNGKey(3)
        mask = jax.random.uniform(jax.random.PRNGKey(4), (90, 60)) < 0.08
        dense = jnp.where(mask, jax.random.normal(key, (90, 60)), 0.0)
        sop = sharded_operator(SparseOp.fromdense(dense), mesh)
        p = jax.random.normal(jax.random.PRNGKey(5), (60,))
        q = jax.random.normal(jax.random.PRNGKey(6), (90,))
        e_mv = float(jnp.max(jnp.abs(sop.mv(p) - dense @ p)))
        e_rmv = float(jnp.max(jnp.abs(sop.rmv(q) - dense.T @ q)))
        s_true = jnp.linalg.svd(dense, compute_uv=False)[:6]
        out = factorize(sop, SVDSpec(method="fsvd_blocked", rank=6),
                        key=jax.random.PRNGKey(8))
        e_s = float(np.max(np.abs(np.asarray(out.s) - np.asarray(s_true)))
                    / float(s_true[0]))
        lr = jax.random.normal(jax.random.PRNGKey(9), (64, 7)) \\
            @ jax.random.normal(jax.random.PRNGKey(10), (7, 48))
        gop = sharded_operator(GramOp(DenseOp(lr)), mesh)
        rk = int(estimate_rank(gop, key=jax.random.PRNGKey(11)).rank)
        print(json.dumps({"mv": e_mv, "rmv": e_rmv, "sigma": e_s,
                          "rank": rk}))
    """)
    assert res["mv"] < 1e-4 and res["rmv"] < 1e-4
    assert res["sigma"] < 1e-5
    assert res["rank"] == 7


def test_fsvd_sharded_rejects_host_loop():
    """Regression (this PR): spec.host_loop=True used to be silently
    honored — a host loop on a sharded operand gathers device scalars
    every iteration, stalling the mesh.  It must be a loud error now."""
    import jax
    import pytest
    from repro.api import SVDSpec, factorize
    from repro.distributed.matvec import sharded_operator
    from repro.launch.mesh import make_mesh
    import repro.distributed.gk_dist  # noqa: F401  (registers fsvd_sharded)
    mesh = make_mesh((jax.device_count(),), ("data",))
    op = sharded_operator(
        jax.random.normal(jax.random.PRNGKey(0), (32, 16)), mesh)
    with pytest.raises(ValueError, match="host_loop"):
        factorize(op, SVDSpec(method="fsvd_sharded", rank=4,
                              host_loop=True),
                  key=jax.random.PRNGKey(1))
    # host_loop=None / False keep working
    out = factorize(op, SVDSpec(method="fsvd_sharded", rank=4),
                    key=jax.random.PRNGKey(1))
    assert out.s.shape == (4,)


def test_estimate_rank_sharded_defaults_to_in_graph(monkeypatch):
    """Regression (this PR): estimate_rank's host-loop default must flip
    to the in-graph loop on sharded operands — the per-iteration host
    gather is the same mesh-wide stall fsvd_sharded rejects."""
    import jax
    import pytest
    import repro.core.gk as gk_mod
    from repro.api import SVDSpec, estimate_rank
    from repro.distributed.matvec import sharded_operator
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((jax.device_count(),), ("data",))
    A = jax.random.normal(jax.random.PRNGKey(0), (40, 9)) \
        @ jax.random.normal(jax.random.PRNGKey(1), (9, 24))
    op = sharded_operator(A, mesh)

    def _no_host_loop(*a, **kw):
        raise AssertionError("sharded estimate_rank took the host loop")

    monkeypatch.setattr(gk_mod, "gk_bidiag_host", _no_host_loop)
    est = estimate_rank(op, key=jax.random.PRNGKey(2))
    assert int(est.rank) == 9
    # an explicit host_loop=True is still the caller's to choose
    with pytest.raises(AssertionError, match="host loop"):
        estimate_rank(op, SVDSpec(host_loop=True),
                      key=jax.random.PRNGKey(2))
    # ... and dense operands keep the paper's early-exit host default
    with pytest.raises(AssertionError, match="host loop"):
        estimate_rank(A, key=jax.random.PRNGKey(2))


def test_sharded_train_step_runs():
    """End-to-end: reduced arch, (2,2,2) pod mesh, one real sharded step."""
    res = run_sub("""
        from repro.launch.mesh import make_mesh
        from repro.launch import input_specs as ispec
        from repro.configs import get_arch
        from repro.configs.base import OptimConfig
        from repro.runtime.steps import build_train_step, init_state
        from repro.data.synthetic import lm_batch, LMBatchSpec

        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        cfg = get_arch("olmoe-1b-7b").reduced()
        opt = OptimConfig(lr=1e-3)
        state = init_state(cfg, opt, jax.random.PRNGKey(0))
        _, state_shard = ispec.state_struct_and_shardings(cfg, opt, mesh)
        state = jax.device_put(state, state_shard)
        step = jax.jit(build_train_step(cfg, opt, mesh),
                       in_shardings=(state_shard, None),
                       donate_argnums=(0,))
        spec = LMBatchSpec(8, 32, cfg.vocab_size)
        with mesh:
            state, metrics = step(state, lm_batch(spec, 0, 0))
            state, metrics = step(state, lm_batch(spec, 0, 1))
        print(json.dumps({"loss": float(metrics["loss"]),
                          "finite": bool(jnp.isfinite(metrics["loss"]))}))
    """)
    assert res["finite"]
