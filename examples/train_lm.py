"""LM training with Krylov low-rank gradient compression (reduced config).

Data-parallel training where the per-layer gradient all-reduce is replaced
by the paper's GK factorization of the implicit mean-gradient operator
(repro.distributed.compression): each Lanczos iteration moves one m-vector +
one n-vector instead of the full m x n gradient.  Uses 8 fake CPU devices.

    python examples/train_lm.py --steps 30 --compress
"""
import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import argparse      # noqa: E402
import time          # noqa: E402

import jax           # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import get_arch                     # noqa: E402
from repro.configs.base import FsvdConfig, OptimConfig  # noqa: E402
from repro.data.synthetic import LMBatchSpec, lm_batch  # noqa: E402
from repro.distributed import compression as C         # noqa: E402
from repro.launch.cache import enable_compile_cache   # noqa: E402
from repro.launch.mesh import make_mesh                # noqa: E402
from repro.models import model as model_mod            # noqa: E402
from repro.optim import make_optimizer                 # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="stablelm-1.6b")
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--compress", action="store_true")
    ap.add_argument("--rank", type=int, default=4)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_arch(args.arch).reduced()
    fcfg = FsvdConfig(compression_rank=args.rank, compression_min_dim=64,
                      max_iters=2 * args.rank)
    ocfg = OptimConfig(lr=1e-3, warmup_steps=2, total_steps=args.steps)
    mesh = make_mesh((8,), ("data",))
    opt_init, opt_update = make_optimizer(ocfg)

    params, _ = model_mod.init_model(cfg, jax.random.PRNGKey(0))
    opt_state = opt_init(params)
    ef = C.init_error_feedback(params, fcfg)

    def local_loss(params, batch):
        return model_mod.loss_fn(params, batch, cfg)[0]

    def dp_step(params, opt_state, ef, batch):
        """shard_map over 'data': local grads -> (compressed) mean -> adamw."""
        def body(params, opt_state, ef, batch):
            # params/opt replicated; batch sharded on batch axis
            grads = jax.grad(local_loss)(params, batch)
            if args.compress:
                mean, ef, stats = C.compressed_mean_grads(
                    grads, ef, "data", fcfg)
                ratio = stats.compressed_bytes / jnp.maximum(
                    stats.dense_bytes, 1.0)
            else:
                nw = jax.lax.psum(1, "data")
                mean = jax.tree.map(lambda g: jax.lax.psum(g, "data") / nw,
                                    grads)
                ratio = jnp.ones(())
            loss = jax.lax.pmean(local_loss(params, batch), "data")
            new_params, new_opt, _ = opt_update(params, opt_state, mean)
            return new_params, new_opt, ef, loss, ratio

        return jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(), P(), P(), P("data")),
            out_specs=(P(), P(), P(), P(), P()),
            check_vma=False)(params, opt_state, ef, batch)

    step = jax.jit(dp_step, donate_argnums=(0, 1, 2))
    spec = LMBatchSpec(args.batch, args.seq, cfg.vocab_size)
    t0 = time.perf_counter()
    for t in range(args.steps):
        batch = lm_batch(spec, 0, t)
        params, opt_state, ef, loss, ratio = step(params, opt_state, ef,
                                                  batch)
        if t % 5 == 0:
            print(f"[lm] step {t:3d}: loss {float(loss):.4f} "
                  f"comm-bytes ratio {float(ratio):.4f}")
    dt = time.perf_counter() - t0
    mode = "compressed" if args.compress else "dense"
    print(f"[lm] {args.steps} {mode} DP steps in {dt:.1f}s; "
          f"final loss {float(loss):.4f}")


if __name__ == "__main__":
    main()
