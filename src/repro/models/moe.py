"""Expert-parallel mixture-of-experts block.

Design (see DESIGN.md §MoE): experts are sharded over the ``model`` mesh axis
(EP) and additionally over ``data`` (FSDP); tokens are sharded over the batch
axes and *replicated* across ``model``.  Each device:

  1. computes the router for its token block (cheap, duplicated across EP),
  2. builds a fixed-capacity ``(E_local, C, D)`` buffer holding exactly the
     tokens routed to *its local experts* (capacity-drop, scatter with
     ``mode='drop'`` so out-of-capacity assignments vanish),
  3. runs the gated expert MLP as one batched einsum over local experts,
  4. scatter-adds gated results back to token positions and ``psum``s over
     the EP axis to combine contributions from all expert owners.

This avoids the GShard one-hot dispatch einsum (whose FLOPs/memory rival the
expert compute itself) and keeps every gather/scatter device-local inside
``shard_map``.  An all-to-all dispatch variant is the documented hillclimb
alternative (§Perf).

Memory note: the buffer-side *gather* (``x[tok_for_slot]``) and buffer-side
*scatter-add* formulations are chosen so the ``(T, k, D)`` per-assignment
tensor is never materialized.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs.base import ModelConfig, MoEConfig
from repro.models.layers import ParamBag, activate

Array = jax.Array


def init_moe(bag: ParamBag, cfg: ModelConfig, dtype, name: str = "moe"):
    moe = cfg.moe
    d = cfg.d_model
    sub = bag.sub(name)
    sub.dense("w_router", (d, moe.num_experts), ("embed", "experts_dim"),
              jnp.float32)
    sub.dense("w_gate", (moe.num_experts, d, moe.d_ff_expert),
              ("experts", "embed", "mlp"), dtype)
    sub.dense("w_up", (moe.num_experts, d, moe.d_ff_expert),
              ("experts", "embed", "mlp"), dtype)
    sub.dense("w_down", (moe.num_experts, moe.d_ff_expert, d),
              ("experts", "mlp", "embed"), dtype)


def _batch_axes(mesh: Mesh) -> tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def _expert_axis(mesh: Mesh) -> Optional[str]:
    return "model" if "model" in mesh.axis_names else None


def _local_moe(x: Array, wr: Array, wg: Array, wu: Array, wd: Array,
               *, moe: MoEConfig, act: str, ep_axis: Optional[str],
               fsdp_axes: tuple[str, ...], renorm: bool) -> tuple[Array, Array]:
    """shard_map body. x: (B_loc, S, D) tokens local; experts local on
    ``ep_axis``; expert weights additionally sharded over ``fsdp_axes`` on
    their d_model dim (all-gathered here, FSDP-style)."""
    B, S, D = x.shape
    T = B * S
    xt = x.reshape(T, D)
    E = moe.num_experts
    k = moe.top_k

    for ax in fsdp_axes:
        wg = jax.lax.all_gather(wg, ax, axis=1, tiled=True)
        wu = jax.lax.all_gather(wu, ax, axis=1, tiled=True)
        wd = jax.lax.all_gather(wd, ax, axis=2, tiled=True)
    E_loc = wg.shape[0]

    # --- router (f32) ---
    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32),
                        wr.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gates, eidx = jax.lax.top_k(probs, k)                 # (T,k)
    if renorm:
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)

    # --- local-expert selection ---
    if ep_axis is not None:
        my_lo = jax.lax.axis_index(ep_axis) * E_loc
    else:
        my_lo = 0
    flat_e = eidx.reshape(-1)                             # (T*k,)
    flat_tok = jnp.repeat(jnp.arange(T), k)
    flat_gate = gates.reshape(-1)
    local_e = flat_e - my_lo
    mine = (local_e >= 0) & (local_e < E_loc)
    key = jnp.where(mine, local_e, E_loc)                 # E_loc = "not mine"
    order = jnp.argsort(key, stable=True)
    sorted_e = key[order]
    counts = jnp.bincount(key, length=E_loc + 1)
    starts = jnp.concatenate([jnp.zeros((1,), counts.dtype),
                              jnp.cumsum(counts)[:-1]])
    slot = jnp.arange(T * k) - starts[sorted_e]

    C = max(int(moe.capacity_factor * T * k / E) , 8)
    keep = (sorted_e < E_loc) & (slot < C)
    # token id per buffer slot; dropped slots point out-of-bounds (=T)
    e_idx = jnp.where(keep, sorted_e, E_loc)
    s_idx = jnp.where(keep, slot, C)
    tok_for_slot = jnp.full((E_loc + 1, C + 1), T, jnp.int32)
    tok_for_slot = tok_for_slot.at[e_idx, s_idx].set(
        flat_tok[order].astype(jnp.int32), mode="drop")
    gate_for_slot = jnp.zeros((E_loc + 1, C + 1), jnp.float32)
    gate_for_slot = gate_for_slot.at[e_idx, s_idx].set(
        flat_gate[order], mode="drop")
    tok_for_slot = tok_for_slot[:E_loc, :C]
    gate_for_slot = gate_for_slot[:E_loc, :C]

    # --- gather -> batched expert MLP -> scatter-add ---
    buf = jnp.take(xt, tok_for_slot.reshape(-1), axis=0, mode="fill",
                   fill_value=0).reshape(E_loc, C, D)
    h_g = jnp.einsum("ecd,edf->ecf", buf, wg)
    h_u = jnp.einsum("ecd,edf->ecf", buf, wu)
    h = activate(h_g, act) * h_u
    out_buf = jnp.einsum("ecf,efd->ecd", h, wd)
    out_buf = out_buf * gate_for_slot[..., None].astype(out_buf.dtype)

    y = jnp.zeros((T + 1, D), out_buf.dtype)
    y = y.at[tok_for_slot.reshape(-1)].add(out_buf.reshape(-1, D), mode="drop")
    y = y[:T]
    if ep_axis is not None:
        y = jax.lax.psum(y, ep_axis)

    # --- aux load-balance loss (Switch style), averaged globally ---
    frac_tokens = jnp.zeros((E,), jnp.float32).at[flat_e].add(1.0) / (T * k)
    frac_probs = probs.mean(0)
    aux = E * jnp.sum(frac_tokens * frac_probs)
    all_axes = tuple(a for a in (fsdp_axes + ((ep_axis,) if ep_axis else ()))
                     if a is not None)
    if all_axes:
        n = functools.reduce(lambda a, b: a * b,
                             [jax.lax.psum(1, ax) for ax in all_axes], 1)
        aux = jax.lax.psum(aux, all_axes) / n
    return y.reshape(B, S, D).astype(x.dtype), aux


def moe_block(p: dict, x: Array, cfg: ModelConfig, mesh: Optional[Mesh],
              renorm: bool = True) -> tuple[Array, Array]:
    """Apply the EP MoE block. Returns (y, aux_loss)."""
    moe = cfg.moe
    if mesh is None:
        # single-device path (smoke tests without a mesh)
        y, aux = _local_moe(x, p["w_router"], p["w_gate"], p["w_up"],
                            p["w_down"], moe=moe, act=cfg.mlp_act,
                            ep_axis=None, fsdp_axes=(), renorm=renorm)
        return y, aux

    baxes = _batch_axes(mesh)
    ep = _expert_axis(mesh)
    fsdp = tuple(a for a in baxes if a == "data")
    body = functools.partial(_local_moe, moe=moe, act=cfg.mlp_act,
                             ep_axis=ep, fsdp_axes=fsdp, renorm=renorm)
    wspec_gu = P(ep, "data" if "data" in mesh.axis_names else None, None)
    wspec_d = P(ep, None, "data" if "data" in mesh.axis_names else None)
    y, aux = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(baxes or None, None, None),   # x: batch-sharded tokens
                  P(None, None),                  # router
                  wspec_gu, wspec_gu, wspec_d),
        out_specs=(P(baxes or None, None, None), P()),
        check_vma=False,
    )(x, p["w_router"], p["w_gate"], p["w_up"], p["w_down"])
    return y, aux
