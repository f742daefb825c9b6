"""allreduce_per_half_step: all-reduces in the compiled left and right GK
half-steps of a sharded operand, mean of the two, counted in the
compiled HLO text."""


def allreduce_count(fn, *args) -> int:
    import jax
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    return sum(line.count(" all-reduce(") + line.count(" all-reduce-start(")
               for line in hlo.splitlines())


def read(run):
    if run.cell.config["layout"] != "rows":
        return None
    import jax.numpy as jnp
    op = run.op
    m, n = op.shape
    k = run.spec.max_iters
    passes = run.spec.reorth_passes
    Q = jnp.zeros((m, k + 1), jnp.float32)
    P = jnp.zeros((n, k), jnp.float32)
    left = allreduce_count(
        lambda o, p, y, B: o.lanczos_step(p, y, 0.5, B, passes=passes), op,
        jnp.zeros((n,)), jnp.zeros((m,)), Q)
    right = allreduce_count(
        lambda o, q, y, B: o.lanczos_rstep(q, y, 0.5, B, passes=passes), op,
        jnp.zeros((m,)), jnp.zeros((n,)), P)
    return (left + right) / 2
