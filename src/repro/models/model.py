"""Model zoo assembly: every assigned architecture behind one API.

    init_model(cfg, key)                  -> (params, logical_axes)
    loss_fn(params, batch, cfg, mesh)     -> (loss, metrics)         [train]
    prefill_step(params, batch, cfg, ..)  -> (last_logits, cache)    [prefill]
    decode_step(params, cache, batch, ..) -> (logits, new_cache)     [decode]
    init_cache(cfg, batch, max_seq)       -> cache pytree

Families: dense / moe / vlm share the decoder-LM skeleton; audio is an
encoder-decoder (whisper); ssm is a Mamba2 stack; hybrid is Zamba2 (Mamba2
backbone + one SHARED attention+MLP block applied every ``attn_every``
layers).

Scale design:
  * homogeneous layer stacks are ``lax.scan``-ned over stacked parameters —
    compile time and HLO size stay O(1) in depth (42-60 layer archs);
  * layer heterogeneity that only changes *masking* (gemma2 local/global
    alternation) is expressed as a scanned per-layer ``window`` int array,
    keeping one scan body;
  * structural heterogeneity (deepseek's dense layer 0, zamba2's shared-attn
    sites) is expressed as unrolled prefix / grouped scans;
  * the LM head + cross-entropy is sequence-chunked (``cfg.ce_chunk``) so the
    (B, S, vocab) logits tensor is never materialized at once — with 256k
    vocabularies that tensor alone would exceed a v5e HBM;
  * per-layer remat (``cfg.remat_policy``) wraps the scan body.
"""
from __future__ import annotations

import functools
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.configs.base import ModelConfig
from repro.models import attention as attn_mod
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import (ParamBag, apply_norm, init_norm, stack_bags)
from repro.models.mlp import init_mlp, mlp

Array = jax.Array
PyTree = Any


# ---------------------------------------------------------------------------
# shared plumbing
# ---------------------------------------------------------------------------

def layer_windows(cfg: ModelConfig) -> jnp.ndarray:
    """(num_layers,) int32 sliding-window per layer; GLOBAL_WINDOW = global."""
    L = cfg.num_layers
    if not cfg.attn_pattern or cfg.sliding_window is None:
        return jnp.full((L,), attn_mod.GLOBAL_WINDOW, jnp.int32)
    pat = [cfg.sliding_window if k == "local" else attn_mod.GLOBAL_WINDOW
           for k in cfg.attn_pattern]
    return jnp.asarray([pat[i % len(pat)] for i in range(L)], jnp.int32)


def _remat(f, policy: str):
    if policy == "none":
        return f
    if policy == "dots":
        return jax.checkpoint(
            f, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return jax.checkpoint(f)


def _pin_batch(x: Array, cfg: ModelConfig, mesh) -> Array:
    """Constrain (B, S, D) activations to batch sharding (see
    ``ModelConfig.pin_activations``).

    Axes that are Manual in the current trace context (e.g. "pod" inside
    the compressed-gradient shard_map) are excluded — the constraint only
    names the Auto axes it can legally pin.
    """
    if not cfg.pin_activations or mesh is None:
        return x
    from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
    from repro.distributed.partition import batch_axes
    baxes = batch_axes(mesh)
    cur = jax.sharding.get_abstract_mesh()
    manual = {name for name, t in zip(cur.axis_names, cur.axis_types)
              if t == AxisType.Manual}
    baxes = tuple(a for a in baxes if a not in manual)
    if not baxes:
        return x
    total = 1
    for a in baxes:
        total *= dict(mesh.shape)[a]
    if x.shape[0] % total:
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(baxes, *([None] * (x.ndim - 1)))))


def _scan_layers(body, x: Array, stacked: PyTree, windows: Array,
                 caches: Optional[PyTree], policy: str):
    """Scan ``body(x, p, window, cache) -> (x, new_cache, aux)`` over layers.

    ``caches=None`` -> body gets cache=None (train / prefill); any non-None
    new_cache the body returns is stacked into the scan output.
    """
    has_cache = caches is not None

    def f(carry, xs):
        x, aux = carry
        if has_cache:
            p, w, cache = xs
        else:
            (p, w), cache = xs, None
        x, new_cache, a = body(x, p, w, cache)
        return (x, aux + a), new_cache

    f = _remat(f, policy)
    xs = (stacked, windows, caches) if has_cache else (stacked, windows)
    (x, aux), new_caches = jax.lax.scan(
        f, (x, jnp.zeros((), jnp.float32)), xs)
    return x, new_caches, aux


def _embed(params: dict, tokens: Array, cfg: ModelConfig) -> Array:
    x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.dtype(cfg.dtype))
    if cfg.embedding_scale:
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    return x


def _head_logits(params: dict, x: Array, cfg: ModelConfig) -> Array:
    """(..., d) -> (..., V) in f32, with the final softcap."""
    if cfg.tie_embeddings:
        logits = jnp.einsum("...d,vd->...v", x,
                            params["embed"].astype(x.dtype),
                            preferred_element_type=jnp.float32)
    else:
        logits = jnp.einsum("...d,dv->...v", x, params["head"],
                            preferred_element_type=jnp.float32)
    if cfg.final_logit_softcap is not None:
        c = cfg.final_logit_softcap
        logits = c * jnp.tanh(logits / c)
    return logits


def _ce_sums(logits: Array, labels: Array, ignore_id: int = -1
             ) -> tuple[Array, Array]:
    """Summed token NLL + valid count, f32 (chunk-accumulation friendly)."""
    logits = logits.astype(jnp.float32)
    valid = labels != ignore_id
    safe = jnp.where(valid, labels, 0)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, safe[..., None], axis=-1)[..., 0]
    nll = jnp.where(valid, lse - gold, 0.0)
    return nll.sum(), valid.sum()


def _chunked_ce(params: dict, x: Array, labels: Array, cfg: ModelConfig
                ) -> tuple[Array, Array]:
    """Sequence-chunked LM-head cross entropy. x: (B,S,d) final-normed.

    Returns (mean nll over valid tokens, n_valid).  The (B, chunk, V) logits
    block is the only vocab-sized live tensor.
    """
    B, S, _ = x.shape
    chunk = cfg.ce_chunk
    if not chunk or S % chunk or S <= chunk:
        nll, n = _ce_sums(_head_logits(params, x, cfg), labels)
        return nll / jnp.maximum(n, 1), n

    nc = S // chunk
    xc = jnp.moveaxis(x.reshape(B, nc, chunk, -1), 1, 0)
    lc = jnp.moveaxis(labels.reshape(B, nc, chunk), 1, 0)

    def f(carry, xs):
        nll_t, n_t = carry
        xi, li = xs
        nll, n = _ce_sums(_head_logits(params, xi, cfg), li)
        return (nll_t + nll, n_t + n), None

    # always full-remat the CE chunk body: the whole point is that the
    # (B, chunk, V) logits block must not be saved as a scan residual.
    f = _remat(f, "nothing")
    (nll, n), _ = jax.lax.scan(
        f, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.int32)), (xc, lc))
    return nll / jnp.maximum(n, 1), n


def _positions(B: int, S: int) -> Array:
    return jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))


class Metrics(NamedTuple):
    loss: Array
    ce: Array
    aux: Array
    n_tokens: Array


# ---------------------------------------------------------------------------
# decoder LM (dense / moe / vlm)
# ---------------------------------------------------------------------------

def _init_decoder_layer(key, cfg: ModelConfig, dtype, kind: str,
                        d_ff: Optional[int] = None) -> tuple[dict, dict]:
    bag = ParamBag(key)
    if cfg.mla is not None:
        attn_mod.init_mla(bag, cfg, dtype)
    else:
        attn_mod.init_gqa(bag, cfg, dtype)
    init_norm(bag, "attn_norm", cfg.d_model, cfg.norm, dtype)
    init_norm(bag, "mlp_norm", cfg.d_model, cfg.norm, dtype)
    if cfg.post_norm:
        init_norm(bag, "post_attn_norm", cfg.d_model, cfg.norm, dtype)
        init_norm(bag, "post_mlp_norm", cfg.d_model, cfg.norm, dtype)
    if kind == "moe":
        moe_mod.init_moe(bag, cfg, dtype)
        if cfg.moe.num_shared_experts:
            init_mlp(bag, cfg.d_model,
                     cfg.moe.num_shared_experts * cfg.moe.d_ff_shared,
                     cfg.mlp_act, dtype, name="shared_mlp")
    else:
        init_mlp(bag, cfg.d_model, d_ff or cfg.d_ff, cfg.mlp_act, dtype)
    return bag.done()


def _layer_kinds(cfg: ModelConfig) -> list[str]:
    if cfg.moe is None:
        return ["dense"] * cfg.num_layers
    kinds = []
    for i in range(cfg.num_layers):
        is_moe = (i >= cfg.moe.moe_start_layer
                  and (i - cfg.moe.moe_start_layer) % cfg.moe.moe_every == 0)
        kinds.append("moe" if is_moe else "dense")
    return kinds


def _init_decoder_lm(cfg: ModelConfig, key) -> tuple[dict, dict]:
    dtype = jnp.dtype(cfg.param_dtype)
    bag = ParamBag(key)
    bag.dense("embed", (cfg.vocab_size, cfg.d_model), ("vocab", "embed"),
              dtype, scale=1.0)
    if not cfg.tie_embeddings:
        bag.dense("head", (cfg.d_model, cfg.vocab_size), ("embed", "vocab"),
                  dtype)
    if cfg.vlm is not None:
        bag.dense("img_proj", (cfg.d_model, cfg.d_model),
                  ("img_in", "embed"), dtype)

    kinds = _layer_kinds(cfg)
    # prefix = leading dense run before the homogeneous tail (deepseek's
    # layer 0); the tail must be homogeneous for the scan.
    n_prefix = 0
    while n_prefix < len(kinds) and cfg.moe is not None \
            and kinds[n_prefix] == "dense":
        n_prefix += 1
    tail_kinds = set(kinds[n_prefix:])
    assert len(tail_kinds) <= 1, f"non-homogeneous tail: {kinds}"
    tail_kind = kinds[-1] if kinds else "dense"

    for i in range(n_prefix):
        p, lg = _init_decoder_layer(bag.next_key(), cfg, dtype, "dense")
        bag.params[f"layer{i}"] = p
        bag.logical[f"layer{i}"] = lg
    layer_bags = [
        _init_decoder_layer(bag.next_key(), cfg, dtype, tail_kind)
        for _ in range(cfg.num_layers - n_prefix)]
    bag.params["layers"], bag.logical["layers"] = stack_bags(layer_bags)
    init_norm(bag, "final_norm", cfg.d_model, cfg.norm, dtype)
    return bag.done()


def _decoder_block(p: dict, x: Array, positions: Array, cfg: ModelConfig,
                   mesh: Optional[Mesh], window, cache, kind: str,
                   collect_kv: bool) -> tuple[Array, Optional[dict], Array]:
    attn_fn = (attn_mod.mla_attention if cfg.mla is not None
               else attn_mod.gqa_attention)
    x = _pin_batch(x, cfg, mesh)
    h = apply_norm(p["attn_norm"], x, cfg.norm)
    a, new_cache = attn_fn(p["attn"], h, positions, cfg, window=window,
                           cache=cache, collect_kv=collect_kv)
    if cfg.post_norm:
        a = apply_norm(p["post_attn_norm"], a, cfg.norm)
    x = x + a
    h = apply_norm(p["mlp_norm"], x, cfg.norm)
    aux = jnp.zeros((), jnp.float32)
    if kind == "moe":
        m, aux = moe_mod.moe_block(p["moe"], h, cfg, mesh)
        if "shared_mlp" in p:
            m = m + mlp(p["shared_mlp"], h, cfg.mlp_act)
    else:
        m = mlp(p["mlp"], h, cfg.mlp_act)
    if cfg.post_norm:
        m = apply_norm(p["post_mlp_norm"], m, cfg.norm)
    return _pin_batch(x + m, cfg, mesh), new_cache, aux


def _decoder_backbone(params: dict, x: Array, positions: Array,
                      cfg: ModelConfig, mesh: Optional[Mesh],
                      caches: Optional[dict], collect_kv: bool
                      ) -> tuple[Array, Optional[dict], Array]:
    """Runs prefix layers (unrolled) + the scanned homogeneous tail."""
    kinds = _layer_kinds(cfg)
    windows = layer_windows(cfg)
    n_prefix = len([k for k in params if k.startswith("layer")
                    and k[5:].isdigit()])
    tail_kind = kinds[-1]
    aux_total = jnp.zeros((), jnp.float32)
    new_prefix_caches = {}
    for i in range(n_prefix):
        cache_i = caches[f"layer{i}"] if caches is not None else None
        x, nc, aux = _decoder_block(
            params[f"layer{i}"], x, positions, cfg, mesh,
            windows[i], cache_i, "dense", collect_kv)
        aux_total = aux_total + aux
        if nc is not None:
            new_prefix_caches[f"layer{i}"] = nc

    def body(x, p, w, cache):
        return _decoder_block(p, x, positions, cfg, mesh, w, cache,
                              tail_kind, collect_kv)

    tail_caches = caches["layers"] if caches is not None else None
    x, new_tail, aux = _scan_layers(body, x, params["layers"],
                                    windows[n_prefix:], tail_caches,
                                    cfg.remat_policy)
    aux_total = aux_total + aux

    new_caches = None
    if caches is not None or (collect_kv and new_tail is not None):
        new_caches = dict(new_prefix_caches)
        new_caches["layers"] = new_tail
    return x, new_caches, aux_total


def _lm_inputs(params: dict, batch: dict, cfg: ModelConfig
               ) -> tuple[Array, Array, Array]:
    """Embed tokens (+ VLM image prefix). Returns (x, positions, labels)."""
    tokens = batch["tokens"]
    x = _embed(params, tokens, cfg)
    labels = batch.get("labels")
    if cfg.vlm is not None and "img_embeds" in batch:
        img = batch["img_embeds"].astype(x.dtype)
        img = jnp.einsum("btd,de->bte", img, params["img_proj"])
        x = jnp.concatenate([img, x], axis=1)
        if labels is not None:
            pad = jnp.full(img.shape[:2], -1, labels.dtype)
            labels = jnp.concatenate([pad, labels], axis=1)
    B, S = x.shape[:2]
    return x, _positions(B, S), labels


# ---------------------------------------------------------------------------
# whisper (audio enc-dec)
# ---------------------------------------------------------------------------

def _init_encdec(cfg: ModelConfig, key) -> tuple[dict, dict]:
    dtype = jnp.dtype(cfg.param_dtype)
    bag = ParamBag(key)
    bag.dense("embed", (cfg.vocab_size, cfg.d_model), ("vocab", "embed"),
              dtype, scale=1.0)
    bag.dense("frame_proj", (cfg.d_model, cfg.d_model), ("img_in", "embed"),
              dtype)

    def enc_layer(k):
        b = ParamBag(k)
        attn_mod.init_gqa(b, cfg, dtype)
        init_norm(b, "attn_norm", cfg.d_model, cfg.norm, dtype)
        init_mlp(b, cfg.d_model, cfg.d_ff, cfg.mlp_act, dtype)
        init_norm(b, "mlp_norm", cfg.d_model, cfg.norm, dtype)
        return b.done()

    def dec_layer(k):
        b = ParamBag(k)
        attn_mod.init_gqa(b, cfg, dtype)
        init_norm(b, "attn_norm", cfg.d_model, cfg.norm, dtype)
        attn_mod.init_cross_attn(b, cfg, dtype)
        init_norm(b, "xattn_norm", cfg.d_model, cfg.norm, dtype)
        init_mlp(b, cfg.d_model, cfg.d_ff, cfg.mlp_act, dtype)
        init_norm(b, "mlp_norm", cfg.d_model, cfg.norm, dtype)
        return b.done()

    enc_bags = [enc_layer(bag.next_key())
                for _ in range(cfg.encdec.encoder_layers)]
    dec_bags = [dec_layer(bag.next_key()) for _ in range(cfg.num_layers)]
    bag.params["enc_layers"], bag.logical["enc_layers"] = stack_bags(enc_bags)
    bag.params["dec_layers"], bag.logical["dec_layers"] = stack_bags(dec_bags)
    init_norm(bag, "enc_norm", cfg.d_model, cfg.norm, dtype)
    init_norm(bag, "final_norm", cfg.d_model, cfg.norm, dtype)
    return bag.done()


def _whisper_encode(params: dict, frames: Array, cfg: ModelConfig) -> Array:
    """frames: (B, T, d) precomputed stub embeddings -> encoder output."""
    x = jnp.einsum("btd,de->bte", frames.astype(jnp.dtype(cfg.dtype)),
                   params["frame_proj"])
    B, T = x.shape[:2]
    pos = _positions(B, T)

    def body(x, p, w, _):
        h = apply_norm(p["attn_norm"], x, cfg.norm)
        a, _ = attn_mod.gqa_attention(p["attn"], h, pos, cfg, window=w,
                                      causal=False)
        x = x + a
        h = apply_norm(p["mlp_norm"], x, cfg.norm)
        return x + mlp(p["mlp"], h, cfg.mlp_act), None, jnp.zeros((), jnp.float32)

    L = cfg.encdec.encoder_layers
    windows = jnp.full((L,), attn_mod.GLOBAL_WINDOW, jnp.int32)
    x, _, _ = _scan_layers(body, x, params["enc_layers"], windows, None,
                           cfg.remat_policy)
    return apply_norm(params["enc_norm"], x, cfg.norm)


def _whisper_decode_stack(params: dict, x: Array, positions: Array,
                          cfg: ModelConfig, enc_out: Optional[Array],
                          caches: Optional[dict], collect_kv: bool
                          ) -> tuple[Array, Optional[dict]]:
    """Decoder layers.  Cross-attention K/V come from ``enc_out`` during
    train/prefill (computed per layer inside the scan) and from the cache
    during decode.

    ``caches`` is the flat stacked dict {"self": {k,v}, "cross_k", "cross_v"}
    with a leading decoder-layer dim.  In decode mode only the self cache is
    re-emitted through the scan (cross K/V are static) and merged back after.
    """
    def body(x, p, w, cache):
        self_cache = cache["self"] if cache is not None else None
        h = apply_norm(p["attn_norm"], x, cfg.norm)
        a, new_self = attn_mod.gqa_attention(p["attn"], h, positions, cfg,
                                             window=w, cache=self_cache,
                                             collect_kv=collect_kv)
        x = x + a
        h = apply_norm(p["xattn_norm"], x, cfg.norm)
        if cache is not None:
            kv = (cache["cross_k"], cache["cross_v"])
        else:
            kv = attn_mod.encode_cross_kv(p["xattn"], enc_out)
        x = x + attn_mod.cross_attention(p["xattn"], h, kv, cfg)
        h = apply_norm(p["mlp_norm"], x, cfg.norm)
        x = x + mlp(p["mlp"], h, cfg.mlp_act)
        new_cache = None
        if cache is not None:
            new_cache = {"self": new_self}
        elif collect_kv:
            new_cache = {"self": new_self, "cross_k": kv[0], "cross_v": kv[1]}
        return x, new_cache, jnp.zeros((), jnp.float32)

    L = cfg.num_layers
    windows = jnp.full((L,), attn_mod.GLOBAL_WINDOW, jnp.int32)
    x, new_caches, _ = _scan_layers(body, x, params["dec_layers"], windows,
                                    caches, cfg.remat_policy)
    if caches is not None:
        new_caches = {"self": new_caches["self"],
                      "cross_k": caches["cross_k"],
                      "cross_v": caches["cross_v"]}
    return x, new_caches


# ---------------------------------------------------------------------------
# mamba2 (ssm) and zamba2 (hybrid)
# ---------------------------------------------------------------------------

def _init_ssm_layer(key, cfg: ModelConfig, dtype) -> tuple[dict, dict]:
    bag = ParamBag(key)
    ssm_mod.init_ssm(bag, cfg, dtype)
    init_norm(bag, "norm", cfg.d_model, cfg.norm, dtype)
    return bag.done()


def _init_mamba(cfg: ModelConfig, key) -> tuple[dict, dict]:
    dtype = jnp.dtype(cfg.param_dtype)
    bag = ParamBag(key)
    bag.dense("embed", (cfg.vocab_size, cfg.d_model), ("vocab", "embed"),
              dtype, scale=1.0)
    if not cfg.tie_embeddings:
        bag.dense("head", (cfg.d_model, cfg.vocab_size), ("embed", "vocab"),
                  dtype)
    bags = [_init_ssm_layer(bag.next_key(), cfg, dtype)
            for _ in range(cfg.num_layers)]
    bag.params["layers"], bag.logical["layers"] = stack_bags(bags)
    init_norm(bag, "final_norm", cfg.d_model, cfg.norm, dtype)
    return bag.done()


def _ssm_stack(params_stacked: PyTree, x: Array, cfg: ModelConfig,
               caches: Optional[PyTree], collect_kv: bool, policy: str
               ) -> tuple[Array, Optional[PyTree]]:
    def body(x, p, w, cache):
        h = apply_norm(p["norm"], x, cfg.norm)
        y, nc = ssm_mod.ssm_block(p["ssm"], h, cfg, cache,
                                  collect_state=collect_kv)
        return x + y, nc, jnp.zeros((), jnp.float32)

    L = jax.tree.leaves(params_stacked)[0].shape[0]
    windows = jnp.zeros((L,), jnp.int32)   # unused by ssm
    x, new_caches, _ = _scan_layers(body, x, params_stacked, windows, caches,
                                    policy)
    return x, new_caches


def _init_zamba(cfg: ModelConfig, key) -> tuple[dict, dict]:
    dtype = jnp.dtype(cfg.param_dtype)
    bag = ParamBag(key)
    bag.dense("embed", (cfg.vocab_size, cfg.d_model), ("vocab", "embed"),
              dtype, scale=1.0)
    bags = [_init_ssm_layer(bag.next_key(), cfg, dtype)
            for _ in range(cfg.num_layers)]
    bag.params["layers"], bag.logical["layers"] = stack_bags(bags)
    shared = bag.sub("shared")
    attn_mod.init_gqa(shared, cfg, dtype)
    init_norm(shared, "attn_norm", cfg.d_model, cfg.norm, dtype)
    init_mlp(shared, cfg.d_model, cfg.hybrid.shared_attn_d_ff, cfg.mlp_act,
             dtype)
    init_norm(shared, "mlp_norm", cfg.d_model, cfg.norm, dtype)
    init_norm(bag, "final_norm", cfg.d_model, cfg.norm, dtype)
    return bag.done()


def n_attn_sites(cfg: ModelConfig) -> int:
    return cfg.num_layers // cfg.hybrid.attn_every


def _shared_attn_block(shared: dict, x: Array, positions: Array,
                       cfg: ModelConfig, cache, collect_kv: bool
                       ) -> tuple[Array, Optional[dict]]:
    h = apply_norm(shared["attn_norm"], x, cfg.norm)
    a, new_cache = attn_mod.gqa_attention(shared["attn"], h, positions, cfg,
                                          cache=cache, collect_kv=collect_kv)
    x = x + a
    h = apply_norm(shared["mlp_norm"], x, cfg.norm)
    return x + mlp(shared["mlp"], h, cfg.mlp_act), new_cache


def _zamba_backbone(params: dict, x: Array, positions: Array,
                    cfg: ModelConfig, caches: Optional[dict],
                    collect_kv: bool) -> tuple[Array, Optional[dict]]:
    """Grouped scan: ``attn_every`` ssm layers then the shared attn block,
    repeated ``n_sites`` times; trailing ssm layers close the stack.

    caches = {"ssm": stacked (L, ...), "attn": stacked (n_sites, ...)}.
    """
    every = cfg.hybrid.attn_every
    L = cfg.num_layers
    sites = n_attn_sites(cfg)
    body_n = sites * every
    shared = params["shared"]

    def split(tree, lo, hi):
        return jax.tree.map(lambda a: a[lo:hi], tree)

    def regroup(tree):
        return jax.tree.map(
            lambda a: a[:body_n].reshape((sites, every) + a.shape[1:]), tree)

    grouped = regroup(params["layers"])
    tail_params = split(params["layers"], body_n, L)
    g_ssm_caches = regroup(caches["ssm"]) if caches is not None else None
    attn_caches = caches["attn"] if caches is not None else None
    tail_caches = (split(caches["ssm"], body_n, L)
                   if caches is not None else None)

    def group_body(carry, xs):
        x = carry
        if caches is not None:
            gp, gssm, gattn = xs
        else:
            gp, = xs
            gssm = gattn = None
        x, new_ssm = _ssm_stack(gp, x, cfg, gssm, collect_kv,
                                cfg.remat_policy)
        x, new_attn = _shared_attn_block(shared, x, positions, cfg, gattn,
                                         collect_kv)
        return x, (new_ssm, new_attn)

    xs = ((grouped, g_ssm_caches, attn_caches) if caches is not None
          else (grouped,))
    x, (new_ssm_g, new_attn) = jax.lax.scan(group_body, x, xs)

    x, new_tail = _ssm_stack(tail_params, x, cfg, tail_caches, collect_kv,
                             cfg.remat_policy) if body_n < L else (x, None)

    new_caches = None
    if caches is not None or collect_kv:
        def flatten_groups(tree):
            return jax.tree.map(
                lambda a: a.reshape((body_n,) + a.shape[2:]), tree)
        new_body = flatten_groups(new_ssm_g)
        if new_tail is not None:
            new_ssm = jax.tree.map(
                lambda a, b: jnp.concatenate([a, b], 0), new_body, new_tail)
        else:
            new_ssm = new_body
        new_caches = {"ssm": new_ssm, "attn": new_attn}
    return x, new_caches


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------

def init_model(cfg: ModelConfig, key) -> tuple[dict, dict]:
    if cfg.family in ("dense", "moe", "vlm"):
        return _init_decoder_lm(cfg, key)
    if cfg.family == "audio":
        return _init_encdec(cfg, key)
    if cfg.family == "ssm":
        return _init_mamba(cfg, key)
    if cfg.family == "hybrid":
        return _init_zamba(cfg, key)
    raise ValueError(f"unknown family {cfg.family!r}")


def _backbone_hidden(params: dict, batch: dict, cfg: ModelConfig,
                     mesh: Optional[Mesh], caches, collect_kv
                     ) -> tuple[Array, Optional[dict], Array, Optional[Array]]:
    """Family dispatch: returns (hidden(B,S,d) normed, caches, aux, labels)."""
    aux = jnp.zeros((), jnp.float32)
    if cfg.family in ("dense", "moe", "vlm"):
        x, positions, labels = _lm_inputs(params, batch, cfg)
        x, new_caches, aux = _decoder_backbone(params, x, positions, cfg,
                                               mesh, caches, collect_kv)
    elif cfg.family == "audio":
        tokens = batch["tokens"]
        labels = batch.get("labels")
        x = _embed(params, tokens, cfg)
        B, S = x.shape[:2]
        enc_out = (_whisper_encode(params, batch["frames"], cfg)
                   if "frames" in batch else None)
        x, new_caches = _whisper_decode_stack(
            params, x, _positions(B, S), cfg, enc_out, caches, collect_kv)
    elif cfg.family == "ssm":
        x = _embed(params, batch["tokens"], cfg)
        labels = batch.get("labels")
        ssm_caches = caches["ssm"] if caches is not None else None
        x, new_ssm = _ssm_stack(params["layers"], x, cfg, ssm_caches,
                                collect_kv, cfg.remat_policy)
        new_caches = ({"ssm": new_ssm}
                      if (new_ssm is not None) else None)
    elif cfg.family == "hybrid":
        x = _embed(params, batch["tokens"], cfg)
        labels = batch.get("labels")
        B, S = x.shape[:2]
        positions = batch.get("positions")
        if positions is None:
            positions = _positions(B, S)
        x, new_caches = _zamba_backbone(params, x, positions, cfg, caches,
                                        collect_kv)
    else:
        raise ValueError(cfg.family)
    x = apply_norm(params["final_norm"], x, cfg.norm)
    return x, new_caches, aux, labels


def loss_fn(params: dict, batch: dict, cfg: ModelConfig,
            mesh: Optional[Mesh] = None) -> tuple[Array, Metrics]:
    """Training loss (next-token CE + MoE aux)."""
    x, _, aux, labels = _backbone_hidden(params, batch, cfg, mesh,
                                         caches=None, collect_kv=False)
    ce, n = _chunked_ce(params, x, labels, cfg)
    loss = ce
    if cfg.moe is not None:
        loss = loss + cfg.moe.aux_loss_weight * aux
    return loss, Metrics(loss=loss, ce=ce, aux=aux, n_tokens=n)


def prefill_step(params: dict, batch: dict, cfg: ModelConfig,
                 mesh: Optional[Mesh] = None) -> tuple[Array, dict]:
    """Run the full prompt, return (last-position logits (B,V), kv cache)."""
    x, caches, _, _ = _backbone_hidden(params, batch, cfg, mesh,
                                       caches=None, collect_kv=True)
    logits = _head_logits(params, x[:, -1, :], cfg)
    return logits, caches


def decode_step(params: dict, cache: dict, batch: dict, cfg: ModelConfig,
                mesh: Optional[Mesh] = None) -> tuple[Array, dict]:
    """One-token decode.  batch = {"tokens": (B,1), "positions": (B,1)}."""
    tokens, positions = batch["tokens"], batch["positions"]
    x = _embed(params, tokens, cfg)
    if cfg.family in ("dense", "moe", "vlm"):
        x, new_caches, _ = _decoder_backbone(params, x, positions, cfg, mesh,
                                             cache, collect_kv=False)
        # positions flow through _decoder_backbone via closure; decode uses
        # the caller-provided positions
    elif cfg.family == "audio":
        x, new_caches = _whisper_decode_stack(params, x, positions, cfg,
                                              None, cache, collect_kv=False)
    elif cfg.family == "ssm":
        x, new_ssm = _ssm_stack(params["layers"], x, cfg, cache["ssm"],
                                False, cfg.remat_policy)
        new_caches = {"ssm": new_ssm}
    elif cfg.family == "hybrid":
        x, new_caches = _zamba_backbone(params, x, positions, cfg, cache,
                                        collect_kv=False)
    else:
        raise ValueError(cfg.family)
    x = apply_norm(params["final_norm"], x, cfg.norm)
    logits = _head_logits(params, x[:, -1, :], cfg)
    return logits, new_caches


def pad_cache_to(cache: dict, cfg: ModelConfig, max_seq: int) -> dict:
    """Pad the *sequence* axis of attention caches from prefill length S to
    ``max_seq`` so decode can append tokens at positions >= S.

    SSM states and whisper cross-attention K/V have no growable axis and are
    left untouched.
    """
    def pad(tree, axis):
        def f(a):
            if a.shape[axis] >= max_seq:
                return a
            widths = [(0, 0)] * a.ndim
            widths[axis] = (0, max_seq - a.shape[axis])
            return jnp.pad(a, widths)
        return jax.tree.map(f, tree)

    if cfg.family in ("dense", "moe", "vlm"):
        out = {}
        for k, v in cache.items():
            out[k] = pad(v, 2 if k == "layers" else 1)
        return out
    if cfg.family == "audio":
        return {"self": pad(cache["self"], 2),
                "cross_k": cache["cross_k"], "cross_v": cache["cross_v"]}
    if cfg.family == "ssm":
        return cache
    if cfg.family == "hybrid":
        return {"ssm": cache["ssm"], "attn": pad(cache["attn"], 2)}
    raise ValueError(cfg.family)


def init_cache(cfg: ModelConfig, batch: int, max_seq: int,
               dtype=None) -> dict:
    """Zeroed decode cache for every family (shape source for input_specs)."""
    dtype = dtype or jnp.dtype(cfg.dtype)
    if cfg.family in ("dense", "moe", "vlm"):
        make = (attn_mod.init_mla_cache if cfg.mla is not None
                else attn_mod.init_gqa_cache)
        one = make(cfg, batch, max_seq, dtype)
        kinds = _layer_kinds(cfg)
        n_prefix = (0 if cfg.moe is None
                    else next((i for i, k in enumerate(kinds) if k == "moe"),
                              0))
        n_tail = cfg.num_layers - n_prefix
        cache = {f"layer{i}": jax.tree.map(jnp.copy, one)
                 for i in range(n_prefix)}
        cache["layers"] = jax.tree.map(
            lambda a: jnp.zeros((n_tail,) + a.shape, a.dtype), one)
        return cache
    if cfg.family == "audio":
        h, hd = cfg.num_heads, cfg.resolved_head_dim
        L = cfg.num_layers
        self_c = attn_mod.init_gqa_cache(cfg, batch, max_seq, dtype)
        return {
            "self": jax.tree.map(
                lambda a: jnp.zeros((L,) + a.shape, a.dtype), self_c),
            "cross_k": jnp.zeros((L, batch, max_seq, h, hd), dtype),
            "cross_v": jnp.zeros((L, batch, max_seq, h, hd), dtype),
        }
    if cfg.family == "ssm":
        one = ssm_mod.init_ssm_cache(cfg, batch, dtype)
        return {"ssm": jax.tree.map(
            lambda a: jnp.zeros((cfg.num_layers,) + a.shape, a.dtype), one)}
    if cfg.family == "hybrid":
        one = ssm_mod.init_ssm_cache(cfg, batch, dtype)
        attn_one = attn_mod.init_gqa_cache(cfg, batch, max_seq, dtype)
        return {
            "ssm": jax.tree.map(
                lambda a: jnp.zeros((cfg.num_layers,) + a.shape, a.dtype),
                one),
            "attn": jax.tree.map(
                lambda a: jnp.zeros((n_attn_sites(cfg),) + a.shape, a.dtype),
                attn_one),
        }
    raise ValueError(cfg.family)
