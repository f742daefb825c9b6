"""Batched LM serving with a KV cache (reduced config on CPU).

Prefill once, then greedy-decode with the per-family cache (GQA KV / MLA
latents / SSD states).  Demonstrates the serve path every decode dry-run
cell lowers.

    PYTHONPATH=src python examples/serve_lm.py --arch zamba2-1.2b --gen 24
"""
import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import ARCHS, get_arch
from repro.launch.cache import enable_compile_cache
from repro.launch.serve import generate
from repro.models import model as model_mod


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ARCHS), default="stablelm-1.6b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=24)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_arch(args.arch).reduced()
    key = jax.random.PRNGKey(0)
    params, _ = model_mod.init_model(cfg, key)
    prompts = jax.random.randint(key, (args.batch, args.prompt_len), 0,
                                 cfg.vocab_size)
    frames = None
    if cfg.family == "audio":
        frames = 0.02 * jax.random.normal(
            key, (args.batch, args.prompt_len, cfg.d_model))

    out = generate(params, cfg, prompts, args.gen, frames)   # compile+run
    t0 = time.perf_counter()
    out = generate(params, cfg, prompts, args.gen, frames)
    jax.block_until_ready(out)
    dt = time.perf_counter() - t0
    toks = args.batch * args.gen
    print(f"[serve] {args.arch} ({cfg.family}): {toks} tokens in {dt:.2f}s "
          f"-> {toks/dt:.1f} tok/s (batch {args.batch})")
    print("[serve] continuations:")
    for row in out[:, args.prompt_len:]:
        print("  ", list(map(int, row)))


if __name__ == "__main__":
    main()
