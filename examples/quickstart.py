"""Quickstart: the paper's three algorithms through the `repro.api` facade.

    PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp

from repro.api import DenseOp, SVDSpec, estimate_rank, factorize
from repro.launch.cache import enable_compile_cache

enable_compile_cache()

# A "huge" low-rank matrix (the paper's synthetic setup, CPU-sized here):
# A = M @ N with Gaussian factors -> numerical rank exactly 50.
key = jax.random.PRNGKey(0)
k1, k2 = jax.random.split(key)
A = jax.random.normal(k1, (4000, 50)) @ jax.random.normal(k2, (50, 2000))

# --- Algorithm 3: numerical rank, no user parameters ---
est = estimate_rank(A, key=key)
print(f"numerical rank: {int(est.rank)} "
      f"(GK terminated after {int(est.iterations)} iterations)")

# --- Algorithm 2: accurate partial SVD (top 10 triplets) ---
spec = SVDSpec(method="fsvd", rank=10, max_iters=120, host_loop=True)
out = factorize(A, spec, key=key)
s_true = jnp.linalg.svd(A, compute_uv=False)[:10]
print("F-SVD sigma:", [f"{x:.1f}" for x in out.s])
print("max |sigma - svd|:", float(jnp.max(jnp.abs(out.s - s_true))))

# --- the R-SVD baseline: same call, different spec ---
rs = factorize(A, SVDSpec(method="rsvd", rank=10, oversample=10), key=key)
print("R-SVD(default) max err:", float(jnp.max(jnp.abs(rs.s - s_true))))

# --- F-SVD through the Pallas kernels (TPU path; interpret on CPU) ---
out_k = factorize(DenseOp(A, backend="pallas"),
                  spec.replace(rank=4, max_iters=60), key=key)
print("kernel-path sigma:", [f"{x:.1f}" for x in out_k.s])

# --- batched partial SVD: vmap the facade over a stacked DenseOp ---
As = jnp.stack([A[:500, :400], A[500:1000, 400:800]])
batched = jax.vmap(
    lambda op: factorize(op, SVDSpec(method="fsvd", rank=4, max_iters=40),
                         key=key))(DenseOp(As))
print("batched sigma shape:", batched.s.shape)   # (2, 4)

# --- Table-2 error metrics + warm-start seam ---
print("errors:", {k: (float(v) if v is not None else None)
                  for k, v in out.errors(A).items()})
out2 = factorize(A, spec, q1=out.warm_start())   # warm-started GK
print("warm-start sigma[0]:", float(out2.s[0]))
