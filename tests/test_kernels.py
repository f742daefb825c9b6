"""Per-kernel allclose sweeps (shapes x dtypes) against the ref.py oracles,
in interpret mode (the kernel body runs in Python on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

SHAPES = [(64, 48, 4), (300, 200, 17), (1024, 512, 64), (100, 700, 5),
          (512, 128, 128)]
DTYPES = [jnp.float32, jnp.bfloat16]


def _tol(dt):
    return dict(rtol=3e-2, atol=3e-2) if dt == jnp.bfloat16 \
        else dict(rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("m,n,k", SHAPES)
@pytest.mark.parametrize("dt", DTYPES)
def test_matvec_fused(m, n, k, dt):
    key = jax.random.PRNGKey(m * n)
    ks = jax.random.split(key, 3)
    A = jax.random.normal(ks[0], (m, n)).astype(dt)
    p = jax.random.normal(ks[1], (n,))
    y = jax.random.normal(ks[2], (m,))
    got = ops.matvec_fused(A, p, y, 0.37)
    want = ref.matvec_fused(A, p, y, 0.37)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               **_tol(dt))


@pytest.mark.parametrize("m,n,k", SHAPES)
@pytest.mark.parametrize("dt", DTYPES)
def test_rmatvec_fused(m, n, k, dt):
    key = jax.random.PRNGKey(m + n)
    ks = jax.random.split(key, 3)
    A = jax.random.normal(ks[0], (m, n)).astype(dt)
    q = jax.random.normal(ks[1], (m,))
    y = jax.random.normal(ks[2], (n,))
    got = ops.rmatvec_fused(A, q, y, 1.7)
    want = ref.rmatvec_fused(A, q, y, 1.7)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               **_tol(dt))


@pytest.mark.parametrize("m,n,k", SHAPES)
@pytest.mark.parametrize("passes", [1, 2])
def test_reorth(m, n, k, passes):
    key = jax.random.PRNGKey(k)
    ks = jax.random.split(key, 2)
    Q = jnp.linalg.qr(jax.random.normal(ks[0], (m, k)))[0]
    v = jax.random.normal(ks[1], (m,))
    got = ops.reorth(v, Q, passes)
    want = ref.reorth(v, Q, passes)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
    # the result is orthogonal to the basis
    assert float(jnp.max(jnp.abs(Q.T @ got))) < 1e-4 * float(
        jnp.linalg.norm(v))


@pytest.mark.parametrize("m,n,k", SHAPES)
@pytest.mark.parametrize("dt", DTYPES)
def test_lowrank_matmul(m, n, k, dt):
    key = jax.random.PRNGKey(m - n + k)
    ks = jax.random.split(key, 3)
    U = jax.random.normal(ks[0], (m, k)).astype(dt)
    s = jnp.abs(jax.random.normal(ks[1], (k,)))
    Vt = jax.random.normal(ks[2], (k, n)).astype(dt)
    got = ops.lowrank_matmul(U, s, Vt)
    want = ref.lowrank_matmul(U, s, Vt)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               **_tol(dt))


# --------------------------------------------------------------------------
# ragged shapes: wrappers pad to tile multiples with zeros — exactness of
# that claim is locked in on non-tile-multiple (m, n) well away from any
# (8, 128)-ish boundary, including the sparse ELL kernel.
# --------------------------------------------------------------------------

RAGGED = [(300, 517), (257, 129), (127, 383), (300, 200)]


@pytest.mark.parametrize("m,n", RAGGED)
def test_matvec_fused_ragged(m, n):
    ks = jax.random.split(jax.random.PRNGKey(m ^ n), 3)
    A = jax.random.normal(ks[0], (m, n))
    p = jax.random.normal(ks[1], (n,))
    y = jax.random.normal(ks[2], (m,))
    np.testing.assert_allclose(np.asarray(ops.matvec_fused(A, p, y, 0.9)),
                               np.asarray(ref.matvec_fused(A, p, y, 0.9)),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("m,n", RAGGED)
def test_rmatvec_fused_ragged(m, n):
    ks = jax.random.split(jax.random.PRNGKey(m + 3 * n), 3)
    A = jax.random.normal(ks[0], (m, n))
    q = jax.random.normal(ks[1], (m,))
    y = jax.random.normal(ks[2], (n,))
    np.testing.assert_allclose(np.asarray(ops.rmatvec_fused(A, q, y, 0.4)),
                               np.asarray(ref.rmatvec_fused(A, q, y, 0.4)),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("m,k", [(300, 17), (517, 5), (129, 31)])
def test_reorth_ragged(m, k):
    ks = jax.random.split(jax.random.PRNGKey(m * k), 2)
    Q = jnp.linalg.qr(jax.random.normal(ks[0], (m, k)))[0]
    v = jax.random.normal(ks[1], (m,))
    np.testing.assert_allclose(np.asarray(ops.reorth(v, Q, 2)),
                               np.asarray(ref.reorth(v, Q, 2)),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("m,n", RAGGED)
def test_lowrank_matmul_ragged(m, n):
    ks = jax.random.split(jax.random.PRNGKey(m - n), 3)
    U = jax.random.normal(ks[0], (m, 7))
    s = jnp.abs(jax.random.normal(ks[1], (7,)))
    Vt = jax.random.normal(ks[2], (7, n))
    np.testing.assert_allclose(np.asarray(ops.lowrank_matmul(U, s, Vt)),
                               np.asarray(ref.lowrank_matmul(U, s, Vt)),
                               rtol=2e-4, atol=2e-4)


# --------------------------------------------------------------------------
# fused GK step pipeline: matvec + CGS products + norm in one kernel chain.
# f32 acceptance is 1e-5 (relative to the candidate's scale) — the kernel
# and the oracle both accumulate f32, so only blocking order differs.
# --------------------------------------------------------------------------

GK_STEP_SHAPES = [(64, 48, 4), (300, 517, 17), (257, 129, 31),
                  (127, 383, 9), (1024, 512, 64), (300, 200, 5)]


def _step_inputs(m, n, k, seed, left=True):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    A = jax.random.normal(ks[0], (m, n))
    x = jax.random.normal(ks[1], (n if left else m,))
    y = jax.random.normal(ks[2], (m if left else n,))
    Q = jnp.linalg.qr(jax.random.normal(ks[3], (m if left else n, k)))[0]
    return A, x, y, Q


@pytest.mark.parametrize("m,n,k", GK_STEP_SHAPES)
@pytest.mark.parametrize("passes", [1, 2, 3])
def test_gk_step_fused(m, n, k, passes):
    A, p, y, Q = _step_inputs(m, n, k, m * n + k)
    got_u, got_b = ops.gk_step_fused(A, p, y, 0.37, Q, passes)
    want_u, want_b = ref.gk_step(A, p, y, 0.37, Q, passes)
    scale = float(jnp.max(jnp.abs(want_u)))
    np.testing.assert_allclose(np.asarray(got_u), np.asarray(want_u),
                               rtol=1e-5, atol=1e-5 * scale)
    np.testing.assert_allclose(float(got_b), float(want_b), rtol=1e-5)
    # the pipeline's CGS output is orthogonal to the basis
    if passes >= 2:
        assert float(jnp.max(jnp.abs(Q.T @ got_u))) < 1e-4 * scale


@pytest.mark.parametrize("m,n,k", GK_STEP_SHAPES)
@pytest.mark.parametrize("passes", [1, 2])
def test_gk_rstep_fused(m, n, k, passes):
    A, q, y, P = _step_inputs(m, n, k, m + 3 * n + k, left=False)
    got_v, got_a = ops.gk_rstep_fused(A, q, y, 1.7, P, passes)
    want_v, want_a = ref.gk_rstep(A, q, y, 1.7, P, passes)
    scale = float(jnp.max(jnp.abs(want_v)))
    np.testing.assert_allclose(np.asarray(got_v), np.asarray(want_v),
                               rtol=1e-5, atol=1e-5 * scale)
    np.testing.assert_allclose(float(got_a), float(want_a), rtol=1e-5)


@pytest.mark.parametrize("m,n,k", [(300, 517, 17), (1024, 512, 64)])
def test_gk_step_fused_bf16_storage(m, n, k):
    """bf16 A/basis storage, f32 accumulation: tracks the f32 oracle to
    bf16 input-rounding accuracy."""
    A, p, y, Q = _step_inputs(m, n, k, m ^ n)
    got_u, got_b = ops.gk_step_fused(A.astype(jnp.bfloat16), p, y, 0.37,
                                     Q.astype(jnp.bfloat16), 2)
    want_u, want_b = ref.gk_step(A, p, y, 0.37, Q, 2)
    scale = float(jnp.max(jnp.abs(want_u)))
    np.testing.assert_allclose(np.asarray(got_u), np.asarray(want_u),
                               rtol=3e-2, atol=3e-2 * scale)
    np.testing.assert_allclose(float(got_b), float(want_b), rtol=3e-2)


def test_gk_step_tile_override():
    A, p, y, Q = _step_inputs(512, 384, 32, 99)
    want_u, want_b = ref.gk_step(A, p, y, 0.9, Q, 2)
    for bm, bn in [(128, 128), (512, 384), (64, 256), (2048, 512)]:
        got_u, got_b = ops.gk_step_fused(A, p, y, 0.9, Q, 2, bm=bm, bn=bn)
        np.testing.assert_allclose(np.asarray(got_u), np.asarray(want_u),
                                   rtol=1e-5, atol=1e-4)
        np.testing.assert_allclose(float(got_b), float(want_b), rtol=1e-5)


# --------------------------------------------------------------------------
# sparse ELL matvec kernel
# --------------------------------------------------------------------------

def _random_sparse(key, m, n, density):
    km, kv = jax.random.split(key)
    mask = jax.random.bernoulli(km, density, (m, n))
    return jnp.where(mask, jax.random.normal(kv, (m, n)), 0.0)


@pytest.mark.parametrize("m,n,density",
                         [(300, 517, 0.02), (257, 129, 0.1),
                          (64, 48, 0.3), (128, 1000, 0.005)])
def test_sparse_matvec_vs_ref(m, n, density):
    from repro.kernels.sparse_matvec import ell_pack
    A = _random_sparse(jax.random.PRNGKey(m * n), m, n, density)
    x = jax.random.normal(jax.random.PRNGKey(1), (n,))
    idx = jnp.stack(jnp.nonzero(A), axis=1)
    vals, cols = ell_pack(A[idx[:, 0], idx[:, 1]], idx, (m, n))
    got = ops.sparse_matvec(vals, cols, x)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(ref.sparse_matvec(vals, cols, x)),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(got), np.asarray(A @ x),
                               rtol=2e-4, atol=2e-4)


def test_sparse_matvec_empty_rows_and_duplicates():
    """Rows with zero entries and duplicate COO coordinates (sum semantics)
    both survive the ELL pack."""
    from repro.kernels.sparse_matvec import ell_pack
    data = jnp.asarray([1.0, 2.0, 3.0, 4.0])
    idx = jnp.asarray([[0, 1], [0, 1], [3, 0], [3, 2]])   # row 0 duplicated;
    x = jnp.asarray([1.0, 10.0, 100.0])                   # rows 1, 2 empty
    vals, cols = ell_pack(data, idx, (5, 3))
    got = ops.sparse_matvec(vals, cols, x)
    np.testing.assert_allclose(np.asarray(got),
                               [30.0, 0.0, 0.0, 403.0, 0.0], rtol=1e-6)


def test_sparse_matvec_tile_override():
    from repro.kernels.sparse_matvec import ell_pack
    A = _random_sparse(jax.random.PRNGKey(5), 200, 150, 0.05)
    x = jax.random.normal(jax.random.PRNGKey(6), (150,))
    idx = jnp.stack(jnp.nonzero(A), axis=1)
    vals, cols = ell_pack(A[idx[:, 0], idx[:, 1]], idx, (200, 150))
    for bm in (32, 64, 256):
        np.testing.assert_allclose(
            np.asarray(ops.sparse_matvec(vals, cols, x, bm=bm)),
            np.asarray(A @ x), rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("backend", ["gpu", "cuda", "rocm"])
def test_kernels_refuse_non_tpu_accelerators(monkeypatch, backend):
    """Interpret mode is for the CPU test backend only: any other non-TPU
    backend raises instead of silently interpreting."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    with pytest.raises(RuntimeError, match="compiled on TPU"):
        ops._interpret()


def test_kernels_interpret_on_cpu_compile_on_tpu(monkeypatch):
    assert ops._interpret() is (jax.default_backend() == "cpu")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ops._interpret() is False


@pytest.mark.parametrize("k,want", [(1, (2048, 512)), (201, (2048, 512)),
                                    (512, (2048, 512)), (1024, (1024, 512)),
                                    (8193, (64, 128))])
def test_gk_step_tiles_shrink_with_basis_width(k, want):
    """Power-of-two tiles (no pad for (2048, 512)-multiple operands) whose
    (block, k) basis tile stays within the VMEM budget."""
    from repro.kernels import gk_step
    bm, bn = gk_step.tiles(k)
    assert (bm, bn) == want
    kp = -(-k // 128) * 128
    assert bn * kp <= max(gk_step._BASIS_ELEMS, 128 * kp)
    assert 2048 % bm == 0 and 512 % bn == 0


def test_kernel_tile_override():
    """Non-default block shapes still correct (hillclimb knob)."""
    key = jax.random.PRNGKey(0)
    A = jax.random.normal(key, (512, 384))
    p = jax.random.normal(jax.random.fold_in(key, 1), (384,))
    y = jax.random.normal(jax.random.fold_in(key, 2), (512,))
    for bm, bn in [(128, 128), (512, 384), (64, 256)]:
        got = ops.matvec_fused(A, p, y, 0.1, bm=bm, bn=bn)
        np.testing.assert_allclose(np.asarray(got),
                                   np.asarray(ref.matvec_fused(A, p, y, 0.1)),
                                   rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# sparse-sign sketch apply (gather-only ELL: fixed ζ slots per sketch row)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,d,b", [(300, 64, 24), (128, 130, 16),
                                   (70, 16, 48), (48, 48, 48)])
def test_sketch_matmat_vs_ref(n, d, b):
    from repro.core.sketch import make_sketch
    sk = make_sketch(jax.random.PRNGKey(n * d + b), n, d,
                     kind="sparse_sign", dtype=jnp.float32)
    X = jax.random.normal(jax.random.PRNGKey(2), (n, b))
    got = ops.sketch_matmat(sk.signs, sk.idx, X)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(ref.sketch_matmat(sk.signs,
                                                            sk.idx, X)),
                               rtol=2e-5, atol=2e-5)
    # both must equal the dense TᵀX with the scatter-built T (duplicate
    # slot indices sum — same semantics on both paths)
    dense = np.asarray(sk.dense())
    np.testing.assert_allclose(np.asarray(got), dense.T @ np.asarray(X),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("n,d,b", [(300, 64, 24), (128, 130, 16),
                                   (70, 16, 48), (600, 48, 520)])
def test_sketch_matmat_right_vs_dense(n, d, b):
    """``right=True`` applies the sketch from the operand side: X T."""
    from repro.core.sketch import make_sketch
    sk = make_sketch(jax.random.PRNGKey(n + d + b), n, d,
                     kind="sparse_sign", dtype=jnp.float32)
    X = jax.random.normal(jax.random.PRNGKey(3), (b, n))
    got = ops.sketch_matmat(sk.signs, sk.idx, X, right=True)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(X) @ np.asarray(sk.dense()),
                               rtol=2e-5, atol=2e-5)


def test_sketch_matmat_tile_override():
    from repro.core.sketch import make_sketch
    sk = make_sketch(jax.random.PRNGKey(9), 200, 96, kind="sparse_sign",
                     dtype=jnp.float32)
    X = jax.random.normal(jax.random.PRNGKey(10), (200, 32))
    want = np.asarray(ref.sketch_matmat(sk.signs, sk.idx, X))
    for bd in (32, 96, 256):
        np.testing.assert_allclose(
            np.asarray(ops.sketch_matmat(sk.signs, sk.idx, X, bd=bd)),
            want, rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# COO scatter-add (count-sketch fold primitive — the first scatter kernel)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("E,m,d", [(300, 37, 20), (128, 128, 128),
                                   (1, 5, 3), (513, 260, 130)])
def test_scatter_add_vs_ref(E, m, d):
    ks = jax.random.split(jax.random.PRNGKey(E * m + d), 3)
    rows = jax.random.randint(ks[0], (E,), 0, m, jnp.int32)
    cols = jax.random.randint(ks[1], (E,), 0, d, jnp.int32)
    vals = jax.random.normal(ks[2], (E,))
    got = ops.scatter_add(rows, cols, vals, (m, d))
    want = ref.scatter_add(rows, cols, vals, (m, d))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-6, atol=2e-6)
    dense = np.zeros((m, d), np.float64)
    np.add.at(dense, (np.asarray(rows), np.asarray(cols)),
              np.asarray(vals, np.float64))
    np.testing.assert_allclose(np.asarray(got), dense, rtol=1e-5, atol=1e-5)


def test_scatter_add_duplicate_slots_bitexact():
    """Forced collisions: duplicate coordinates SUM, and on dyadic values
    (exact f32 addition) the kernel matches the dense einsum oracle
    bit-for-bit — the acceptance contract for count-sketch semantics."""
    rows = jnp.asarray([3, 3, 3, 0, 3, 1, 1], jnp.int32)
    cols = jnp.asarray([1, 1, 1, 0, 1, 2, 2], jnp.int32)
    vals = jnp.asarray([0.25, 0.5, 1.25, -2.0, -0.75, 8.0, -8.0],
                       jnp.float32)
    got = np.asarray(ops.scatter_add(rows, cols, vals, (5, 4)))
    want = np.asarray(ref.scatter_add(rows, cols, vals, (5, 4)))
    np.testing.assert_array_equal(got, want)
    assert got[3, 1] == np.float32(1.25)       # 0.25+0.5+1.25-0.75
    assert got[1, 2] == np.float32(0.0)        # +8 and -8 annihilate
    assert got[0, 0] == np.float32(-2.0)


def test_scatter_add_empty_and_padding():
    """E=0 returns zeros; block-multiple padding entries (0,0,0) are exact
    — an all-duplicates stream at (0, 0) must not double-count pads."""
    z = ops.scatter_add(jnp.zeros((0,), jnp.int32),
                        jnp.zeros((0,), jnp.int32),
                        jnp.zeros((0,), jnp.float32), (4, 6))
    np.testing.assert_array_equal(np.asarray(z), np.zeros((4, 6)))
    E = 200                                     # pads to 256 at be=128
    rows = jnp.zeros((E,), jnp.int32)
    cols = jnp.zeros((E,), jnp.int32)
    vals = jnp.ones((E,), jnp.float32)
    got = np.asarray(ops.scatter_add(rows, cols, vals, (3, 3)))
    assert got[0, 0] == np.float32(E)
    assert np.abs(got).sum() == np.float32(E)


def test_scatter_add_block_override():
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    E, m, d = 400, 50, 60
    rows = jax.random.randint(ks[0], (E,), 0, m, jnp.int32)
    cols = jax.random.randint(ks[1], (E,), 0, d, jnp.int32)
    vals = jax.random.normal(ks[2], (E,))
    want = np.asarray(ref.scatter_add(rows, cols, vals, (m, d)))
    for be in (32, 100, 512):
        np.testing.assert_allclose(
            np.asarray(ops.scatter_add(rows, cols, vals, (m, d), be=be)),
            want, rtol=2e-6, atol=2e-6)
