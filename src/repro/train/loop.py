"""Training step builders: auto-sharded (jit) and pod-explicit (shard_map).

``make_train_step``      — pure-jit step; XLA inserts all collectives
                           (FSDP all-gathers, grad reduce-scatters).
``make_pod_train_step``  — multi-pod production step: within-pod sharding is
                           auto (XLA over data/model axes) while the cross-pod
                           gradient reduction is *explicit*, goes through the
                           COUNTDOWN-instrumented ``cd_psum`` (artificial
                           barrier + timeout-governed slack, per the paper),
                           and can be int8-compressed (beyond-paper knob).
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.instrument import cd_psum
from repro.dist.compression import compressed_psum
from repro.models.transformer import loss_fn
from repro.train.optimizer import OptConfig, adamw_update, init_opt_state


@dataclass(frozen=True)
class TrainConfig:
    microbatch: int = 0          # 0 = no accumulation; else per-step microbatch
    pod_reduce: str = "auto"     # auto | manual | compressed
    instrument_axis: str = "pod"
    grad_reduce_dtype: str = ""  # "" = grads keep their natural dtype;
                                 # "bfloat16" halves cross-device reduce wire


def _grads(cfg, params, batch, reduce_dtype: str = ""):
    (loss, metrics), grads = jax.value_and_grad(
        lambda p: loss_fn(cfg, p, batch), has_aux=True
    )(params)
    if reduce_dtype:
        # cast before XLA inserts the cross-device reduction: halves the
        # all-reduce/reduce-scatter wire bytes (AdamW re-ups to fp32)
        from repro.models.layers import dtype_of

        dt = dtype_of(reduce_dtype)
        grads = jax.tree.map(lambda g: g.astype(dt), grads)
    return loss, metrics, grads


def _accumulated_grads(cfg, params, batch, microbatch: int):
    """lax.scan over microbatches — memory-bounded gradient accumulation."""
    b = batch["tokens"].shape[0]
    n = b // microbatch
    assert n * microbatch == b, "global batch must be divisible by microbatch"
    split = jax.tree.map(
        lambda a: a.reshape((n, microbatch) + a.shape[1:]) if a.ndim >= 1 else a, batch
    )

    def body(carry, mb):
        acc, loss_sum = carry
        loss, _, g = _grads(cfg, params, mb)
        acc = jax.tree.map(jnp.add, acc, g)
        return (acc, loss_sum + loss), None

    zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    (acc, loss_sum), _ = jax.lax.scan(body, (zeros, jnp.zeros((), jnp.float32)), split)
    grads = jax.tree.map(lambda g: g / n, acc)
    return loss_sum / n, {}, grads


def make_train_step(
    cfg, opt_cfg: OptConfig, train_cfg: TrainConfig = TrainConfig()
) -> Callable:
    """(state, batch) -> (state, metrics); state = {params, opt}."""

    def train_step(state: Dict[str, Any], batch: Dict[str, Any]):
        params = state["params"]
        if train_cfg.microbatch:
            loss, metrics, grads = _accumulated_grads(cfg, params, batch, train_cfg.microbatch)
        else:
            loss, metrics, grads = _grads(cfg, params, batch, train_cfg.grad_reduce_dtype)
        new_params, new_opt, opt_metrics = adamw_update(params, grads, state["opt"], opt_cfg)
        out_metrics = {"loss": loss, **metrics, **opt_metrics}
        return {"params": new_params, "opt": new_opt}, out_metrics

    return train_step


def make_pod_train_step(
    cfg, opt_cfg: OptConfig, mesh: Mesh, train_cfg: TrainConfig = TrainConfig()
) -> Callable:
    """Cross-pod-explicit train step (requires a 'pod' mesh axis).

    Gradients are computed per pod (auto-sharded over data/model inside),
    then explicitly reduced over 'pod' via the instrumented collective.
    """
    if "pod" not in mesh.axis_names:
        raise ValueError("make_pod_train_step needs a mesh with a 'pod' axis")
    npod = mesh.shape["pod"]

    def reduce_grads(grads):
        if train_cfg.pod_reduce == "compressed":
            return compressed_psum(grads, "pod", mean=True)
        summed = cd_psum(grads, "pod")
        return jax.tree.map(lambda g: g / npod, summed)

    def per_pod(params, batch):
        # within-pod sharding is auto (XLA over the data axes); only the
        # cross-pod reduction is explicit + instrumented.  Constraints
        # inside the manual-'pod' region must not name 'pod', nor shard
        # the logits over 'model': XLA's SPMD partitioner aborts when it
        # reshards the loss's vocab gather across the region's device
        # groups.
        from repro.dist.sharding import activation_constraint_fn
        from repro.models import hooks

        old = hooks._CONSTRAIN
        hooks.install_constraint(
            activation_constraint_fn(mesh, exclude={"pod", "model"}))
        try:
            if train_cfg.microbatch:
                loss, _, grads = _accumulated_grads(
                    cfg, params, batch, train_cfg.microbatch)
            else:
                loss, _, grads = _grads(cfg, params, batch)
        finally:
            hooks.install_constraint(old)
        grads = reduce_grads(grads)
        loss = jax.lax.pmean(loss, "pod")
        return loss, grads

    region = jax.shard_map(
        per_pod,
        mesh=mesh,
        in_specs=(P(), P("pod")),
        out_specs=(P(), P()),
        axis_names={"pod"},
        check_vma=False,
    )

    def train_step(state: Dict[str, Any], batch: Dict[str, Any]):
        loss, grads = region(state["params"], batch)
        new_params, new_opt, opt_metrics = adamw_update(
            state["params"], grads, state["opt"], opt_cfg
        )
        return {"params": new_params, "opt": new_opt}, {"loss": loss, **opt_metrics}

    return train_step


def init_state(cfg, opt_cfg: OptConfig, key) -> Dict[str, Any]:
    from repro.models.transformer import init_params

    params = init_params(cfg, key)
    return {"params": params, "opt": init_opt_state(params, opt_cfg)}
