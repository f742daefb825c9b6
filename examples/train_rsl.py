"""End-to-end driver: the paper's RSL application at 1e8-parameter scale.

Learns a rank-5 similarity metric W in R^{10000 x 10000} (1e8 entries — the
paper's "huge matrix" regime) with Riemannian mini-batch SGD (Alg 4), using
the F-SVD retraction (Alg 2) on an IMPLICIT operator: the dense W is never
materialized anywhere in the training loop — the point, tangent vectors and
retraction all live in factored form, so memory is O((d1+d2) r) ~ 100k
floats instead of 1e8.

This loop is the paper's §V workload made literal: thousands of partial
SVDs of operators that *drift slowly* between steps.  Two tracking layers
exploit that:

  * the retraction runs in *tracking* mode (``RSGDOptions.track``,
    default): each step's F-SVD warm-starts from the current point's own
    factors inside the compiled step — no cold random-start solve per
    step (``--no-track`` restores the paper's literal cold retraction);
  * the gradient-spectrum monitor is a ``repro.api.Session`` on the
    drifting batch-gradient operator: warm-started refine solves with a
    restart-vs-refine decision from the subspace angle, residual history
    for free, and checkpointable state (``--session-dir``).

A dense-SVD retraction at this size is ~1e12 flops/step; the F-SVD step is
~1e7.  Run it:

    PYTHONPATH=src python examples/train_rsl.py --steps 300

``main(argv)`` is also the callable entry (``chip_smoke.py`` drives a few
steps through it) and returns the learned point and per-step losses.
"""
import argparse
import time

import jax
import jax.numpy as jnp

from repro.api import SVDSpec, session
from repro.core import manifold as mf
from repro.core import rsgd
from repro.data.synthetic import make_rsl_dataset, rsl_batch
from repro.launch.cache import enable_compile_cache


def main(argv=None) -> dict:
    """Train; returns ``{"W", "losses", "compile_s", "step_s"}`` — the
    learned point, every step's loss, the first (compiling) call's wall
    time and the mean wall time of the timed steps."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--d1", type=int, default=10000)
    ap.add_argument("--d2", type=int, default=10000)
    ap.add_argument("--rank", type=int, default=5)
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3.0)
    ap.add_argument("--fsvd-iters", type=int, default=20,
                    help="paper Fig 2: 20 = 'lower iter', 35 = 'higher'")
    ap.add_argument("--n-train", type=int, default=8192)
    ap.add_argument("--no-track", action="store_true",
                    help="cold keyed retraction solves (paper-literal "
                         "Alg 4) instead of warm-started tracking")
    ap.add_argument("--grad-spectrum", action="store_true",
                    help="track the batch-gradient operator's top spectrum "
                         "with a repro.api.Session (logged every 50 steps)")
    ap.add_argument("--session-dir", default=None,
                    help="checkpoint/resume the gradient-spectrum session "
                         "state under this directory")
    args = ap.parse_args(argv)
    enable_compile_cache()

    print(f"[rsl] W: {args.d1} x {args.d2} rank {args.rank} "
          f"({args.d1 * args.d2 / 1e6:.0f}M entries, never materialized)")
    key = jax.random.PRNGKey(0)
    ds = make_rsl_dataset(key, args.n_train, args.d1, args.d2, args.rank,
                          noise=0.05)
    W = mf.random_point(jax.random.fold_in(key, 1), args.d1, args.d2,
                        args.rank)
    opts = rsgd.RSGDOptions(lr=args.lr, fsvd_iters=args.fsvd_iters,
                            track=not args.no_track)
    mode = ("tracking (warm-started F-SVD)" if opts.track
            else "cold keyed F-SVD (paper-literal)")
    print(f"[rsl] retraction: {mode}")
    step = rsgd.make_step(opts)

    grad_sess = None
    if args.grad_spectrum:
        b0 = rsl_batch(ds, 0, 0, args.batch)
        g0 = rsgd.batch_euclidean_grad(W, b0["x"], b0["v"], b0["y"],
                                       opts.loss, opts.weight_decay)
        # the gradient drifts slowly along the trajectory: a Session
        # re-solves it warm from the previous step's Ritz basis.
        grad_sess = session(g0.op, SVDSpec(method="fsvd", rank=args.rank),
                            key=jax.random.fold_in(key, 2))
        if args.session_dir and grad_sess.load_latest(args.session_dir):
            print(f"[rsl] gradient-spectrum session resumed at solve "
                  f"{grad_sess.solves}")

    b = rsl_batch(ds, 0, 0, args.batch)
    t0 = time.perf_counter()
    jax.block_until_ready(step(W, b["x"], b["v"], b["y"], key))  # compile
    compile_s = time.perf_counter() - t0
    losses = []
    t0 = time.perf_counter()
    for t in range(args.steps):
        b = rsl_batch(ds, 0, t, args.batch)
        W, loss = step(W, b["x"], b["v"], b["y"], jax.random.fold_in(key, t))
        losses.append(loss)
        if t % 50 == 0:
            acc = float(rsgd.accuracy(W, b["x"], b["v"], b["y"]))
            msg = (f"[rsl] step {t:4d}: loss {float(loss):.4f} "
                   f"batch-acc {acc * 100:.1f}%")
            if grad_sess is not None:
                g = rsgd.batch_euclidean_grad(W, b["x"], b["v"], b["y"],
                                              opts.loss, opts.weight_decay)
                gf = grad_sess.update(g.op)
                rec = grad_sess.history[-1]
                msg += (f" | grad sigma1 {float(gf.s[0]):.3e} "
                        f"({rec['kind']}, {rec['iterations']} GK iters)")
            print(msg)
    jax.block_until_ready(W.s)
    dt = time.perf_counter() - t0
    losses = [float(x) for x in losses]
    acc = float(rsgd.accuracy(W, ds.X, ds.V, ds.y))
    print(f"[rsl] {args.steps} steps in {dt:.1f}s "
          f"({dt / args.steps * 1e3:.1f} ms/step); train acc {acc*100:.1f}%")
    print(f"[rsl] learned spectrum: {[f'{x:.2f}' for x in W.s]}")
    s_true = ds.true_spectrum()
    print(f"[rsl] planted spectrum (top-5): "
          f"{[f'{x:.2f}' for x in s_true[:5]]}")
    if grad_sess is not None:
        counts = grad_sess.counts()
        print(f"[rsl] gradient-spectrum session: {grad_sess.solves} solves "
              f"({counts['refine']} refined, {counts['restart']} restarts)")
        if args.session_dir:
            grad_sess.save(args.session_dir)
            print(f"[rsl] session state saved to {args.session_dir}")
    return {"W": W, "losses": losses, "compile_s": compile_s,
            "step_s": dt / max(args.steps, 1)}


if __name__ == "__main__":
    main()
