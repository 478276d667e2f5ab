"""Donated, microbatched train step.

Structure (per 1000+-node posture):

* grads accumulated over microbatches with ``lax.scan`` (sequential, so
  peak activation memory is one microbatch);
* loss/grads in fp32 accumulators, params in model dtype;
* optimizer selected per model size (AdamW; Adafactor >= ~100B params);
* global grad-norm clipping;
* optional int8 error-feedback compression hook for the cross-pod
  reduction (wired in the shard_map variant; under pjit/GSPMD the 'pod'
  reduction is fused into the same all-reduce, so compression is exposed
  as an opt-in shard_map path — see repro.optim.compression).

The returned function is pure; callers jit it with donated params/opt
state and sharded inputs (see repro.launch.train / dryrun).
"""

from __future__ import annotations

import functools
from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import transformer as T
from repro.optim import adafactor, adamw, schedule as sched

ADAFACTOR_THRESHOLD = 100e9


class TrainState(NamedTuple):
    params: dict
    opt: tuple
    step: jax.Array


def select_optimizer(cfg: ModelConfig) -> str:
    return "adafactor" if cfg.param_count() >= ADAFACTOR_THRESHOLD \
        else "adamw"


def init_state(key, cfg: ModelConfig, optimizer: Optional[str] = None
               ) -> TrainState:
    params = T.init_params(key, cfg)
    optimizer = optimizer or select_optimizer(cfg)
    opt = adamw.init(params) if optimizer == "adamw" \
        else adafactor.init(params)
    return TrainState(params=params, opt=opt,
                      step=jnp.zeros((), jnp.int32))


def clip_by_global_norm(grads, max_norm: float):
    leaves = jax.tree.leaves(grads)
    gn = jnp.sqrt(sum(jnp.sum(g.astype(jnp.float32) ** 2)
                      for g in leaves))
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(gn, 1e-9))
    return jax.tree.map(lambda g: (g.astype(jnp.float32) * scale)
                        .astype(g.dtype), grads), gn


def make_train_step(cfg: ModelConfig, *, optimizer: Optional[str] = None,
                    peak_lr: float = 3e-4, warmup_steps: int = 100,
                    total_steps: int = 10_000, weight_decay: float = 0.1,
                    grad_clip: float = 1.0, microbatches: int = 1,
                    remat: bool = True, n_loss_chunks: int = 8
                    ) -> Callable:
    """Build the (params-donatable) train step for an architecture."""
    optimizer = optimizer or select_optimizer(cfg)
    opt_update = adamw.update if optimizer == "adamw" \
        else adafactor.update

    def loss_of(params, batch):
        loss, metrics = T.loss_fn(params, cfg, batch,
                                  n_chunks=n_loss_chunks, remat=remat)
        return loss, metrics

    def grads_of(params, batch):
        if microbatches == 1:
            (loss, metrics), grads = jax.value_and_grad(
                loss_of, has_aux=True)(params, batch)
            return loss, metrics, grads

        def split(x):
            b = x.shape[0]
            return x.reshape((microbatches, b // microbatches)
                             + x.shape[1:])

        micro = jax.tree.map(split, batch)
        zero = jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)

        def body(acc, mb):
            g_acc, l_acc = acc
            (loss, _), grads = jax.value_and_grad(
                loss_of, has_aux=True)(params, mb)
            g_acc = jax.tree.map(
                lambda a, g: a + g.astype(jnp.float32), g_acc, grads)
            return (g_acc, l_acc + loss), None

        (g_sum, l_sum), _ = jax.lax.scan(body, (zero, 0.0), micro)
        grads = jax.tree.map(lambda g: g / microbatches, g_sum)
        return l_sum / microbatches, {}, grads

    def train_step(state: TrainState, batch) -> Tuple[TrainState, dict]:
        loss, metrics, grads = grads_of(state.params, batch)
        with jax.named_scope("grad_clip"):
            grads, gnorm = clip_by_global_norm(grads, grad_clip)
        with jax.named_scope("optimizer"):
            lr = sched.warmup_cosine(state.step, peak_lr=peak_lr,
                                     warmup_steps=warmup_steps,
                                     total_steps=total_steps)
            params, opt = opt_update(grads, state.opt, state.params,
                                     lr=lr, weight_decay=weight_decay)
        new_state = TrainState(params=params, opt=opt,
                               step=state.step + 1)
        out_metrics = {"loss": loss, "grad_norm": gnorm, "lr": lr}
        out_metrics.update({k: v for k, v in (metrics or {}).items()})
        return new_state, out_metrics

    return train_step
