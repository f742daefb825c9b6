"""Pure step functions shared by the trainer, the serving loop and the
multi-pod dry-run (the dry-run lowers exactly what the trainer executes).
"""
from __future__ import annotations

import functools
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs.base import FsvdConfig, ModelConfig, OptimConfig, RunConfig
from repro.models import model as model_mod
from repro.optim import make_optimizer

Array = jax.Array
PyTree = Any


class TrainState(NamedTuple):
    params: PyTree
    opt: Any               # optim.OptState


def init_state(cfg: ModelConfig, optim_cfg: OptimConfig, key) -> TrainState:
    params, _ = model_mod.init_model(cfg, key)
    opt_init, _ = make_optimizer(optim_cfg)
    return TrainState(params, opt_init(params))


def build_train_step(model_cfg: ModelConfig, optim_cfg: OptimConfig,
                     mesh: Optional[Mesh] = None, nan_guard: bool = True):
    """(state, batch) -> (new_state, metrics dict).

    The NaN guard is *in-graph*: a non-finite loss turns the whole update
    into a no-op select (no host round-trip, SPMD-consistent across pods) and
    is reported in ``metrics["skipped"]`` for the host-side counter.
    """
    _, opt_update = make_optimizer(optim_cfg)

    def train_step(state: TrainState, batch: dict):
        def lf(params):
            loss, met = model_mod.loss_fn(params, batch, model_cfg, mesh)
            return loss, met

        (loss, met), grads = jax.value_and_grad(lf, has_aux=True)(state.params)
        new_params, new_opt, stats = opt_update(state.params, state.opt, grads)
        metrics = {"loss": loss, "ce": met.ce, "aux": met.aux,
                   "n_tokens": met.n_tokens, **stats}
        if nan_guard:
            ok = jnp.isfinite(loss) & jnp.isfinite(stats["grad_norm"])
            new_params = jax.tree.map(
                lambda n, o: jnp.where(ok, n, o), new_params, state.params)
            new_opt = jax.tree.map(
                lambda n, o: jnp.where(ok, n, o), new_opt, state.opt)
            metrics["skipped"] = (~ok).astype(jnp.int32)
        return TrainState(new_params, new_opt), metrics

    return train_step


def build_compressed_train_step(model_cfg: ModelConfig,
                                optim_cfg: OptimConfig, mesh: Mesh,
                                fsvd_cfg: FsvdConfig,
                                nan_guard: bool = True):
    """Multi-pod train step with Krylov gradient compression on the "pod"
    axis (the DCN hop — the slow, expensive link at 1000-node scale).

    Structure: ``shard_map`` is MANUAL over "pod" only (``auto`` covers
    data/model, so FSDP/TP inside each pod is unchanged GSPMD); each pod
    computes gradients on its batch shard, then the cross-pod mean of every
    large 2-D (or stacked per-layer) gradient is exchanged as GK factors —
    ``k (m+n)`` floats over DCN instead of ``m n`` (repro.distributed.
    compression).  Small leaves ride a plain psum.

    Note: per-step error feedback is disabled here (it would add an f32
    params-sized residual per pod); the examples/tests exercise EF on the
    pure-DP path.  MoE archs keep their inner EP shard_map and are not
    supported on this path — compression applies to their dense submatrices
    via the default path instead.
    """
    assert "pod" in mesh.axis_names, "compressed step needs a pod axis"
    from repro.distributed import compression as C
    _, opt_update = make_optimizer(optim_cfg)
    fcfg = FsvdConfig(**{**fsvd_cfg.__dict__, "error_feedback": False})
    auto = frozenset(a for a in mesh.axis_names if a != "pod")

    def train_step(state: TrainState, batch: dict):
        def pod_body(params, batch):
            def lf(p):
                loss, met = model_mod.loss_fn(p, batch, model_cfg, mesh)
                return loss, met

            (loss, met), grads = jax.value_and_grad(lf, has_aux=True)(params)
            ef = jax.tree.map(lambda g: jnp.zeros((), jnp.float32), grads)
            mean, _, stats = C.compressed_mean_grads(grads, ef, "pod", fcfg)
            loss = jax.lax.pmean(loss, "pod")
            return mean, loss, met.ce, met.aux, met.n_tokens, \
                stats.dense_bytes, stats.compressed_bytes

        grads, loss, ce, aux, n_tok, dense_b, comp_b = jax.shard_map(
            pod_body, mesh=mesh,
            in_specs=(P(), P("pod")),
            out_specs=(P(), P(), P(), P(), P(), P(), P()),
            axis_names={"pod"}, check_vma=False,
        )(state.params, batch)

        new_params, new_opt, stats = opt_update(state.params, state.opt,
                                                grads)
        metrics = {"loss": loss, "ce": ce, "aux": aux, "n_tokens": n_tok,
                   "comm_dense_bytes": dense_b,
                   "comm_compressed_bytes": comp_b, **stats}
        if nan_guard:
            ok = jnp.isfinite(loss) & jnp.isfinite(stats["grad_norm"])
            new_params = jax.tree.map(
                lambda n, o: jnp.where(ok, n, o), new_params, state.params)
            new_opt = jax.tree.map(
                lambda n, o: jnp.where(ok, n, o), new_opt, state.opt)
            metrics["skipped"] = (~ok).astype(jnp.int32)
        return TrainState(new_params, new_opt), metrics

    return train_step


def build_eval_step(model_cfg: ModelConfig, mesh: Optional[Mesh] = None):
    def eval_step(params, batch):
        loss, met = model_mod.loss_fn(params, batch, model_cfg, mesh)
        return {"loss": loss, "ce": met.ce, "n_tokens": met.n_tokens}
    return eval_step


def build_prefill_step(model_cfg: ModelConfig, mesh: Optional[Mesh] = None):
    def prefill(params, batch):
        return model_mod.prefill_step(params, batch, model_cfg, mesh)
    return prefill


def build_decode_step(model_cfg: ModelConfig, mesh: Optional[Mesh] = None):
    def decode(params, cache, batch):
        return model_mod.decode_step(params, cache, batch, model_cfg, mesh)
    return decode
