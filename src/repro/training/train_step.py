"""Train step factory: microbatched gradient accumulation + AdamW.

Distributed-optimization features (per DESIGN.md):
- microbatch accumulation via ``lax.scan`` bounds activation memory;
- optional gradient compression: accumulated grads are cast to bf16 before
  the (pjit-induced) data-axis all-reduce, halving collective bytes;
- parameters/optimizer state are donated at the jit boundary by the
  launcher, so the update is in-place on device.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.training import schedule
from repro.training.adafactor import AdafactorState, adafactor_update, \
    init_opt_state as init_adafactor
from repro.training.adamw import AdamWState, adamw_update, \
    init_opt_state as init_adamw
from repro.training.loss import lm_loss


@dataclass(frozen=True)
class TrainHyper:
    base_lr: float = 3e-4
    warmup: int = 100
    total_steps: int = 10_000
    weight_decay: float = 0.1
    clip_norm: float = 1.0
    b1: float = 0.9
    b2: float = 0.95
    microbatches: int = 1
    grad_dtype: str = "float32"  # "bfloat16" = compressed grad all-reduce
    aux_weight: float = 0.01
    optimizer: str = "adamw"     # "adamw" | "adafactor" (factored, low-mem)


def _split_microbatches(batch: Dict[str, jax.Array], m: int, data_axes=None):
    def split(x):
        b = x.shape[0]
        assert b % m == 0, f"batch {b} not divisible by {m} microbatches"
        out = x.reshape(m, b // m, *x.shape[1:])
        if data_axes:
            # keep the batch dim (axis 1) data-sharded; the microbatch axis
            # (axis 0) must stay unsharded or every scan step would gather
            from jax.sharding import PartitionSpec as P
            spec = P(None, data_axes, *([None] * (x.ndim - 1)))
            out = jax.lax.with_sharding_constraint(out, spec)
        return out
    return jax.tree.map(split, batch)


def make_train_step(cfg: ModelConfig, hyper: TrainHyper, data_axes=None):
    """Returns train_step(params, opt_state, batch) -> (params, opt, metrics).

    The returned function is pure and pjit-able; the launcher wraps it with
    jax.jit + shardings + donation. ``data_axes`` (e.g. ("pod","data"))
    enables the microbatch-split sharding constraint when lowering under a
    mesh.
    """
    grad_dtype = jnp.bfloat16 if hyper.grad_dtype == "bfloat16" else jnp.float32

    def loss_fn(params, microbatch):
        return lm_loss(params, cfg, microbatch, aux_weight=hyper.aux_weight)

    def train_step(params, opt_state: AdamWState, batch):
        m = hyper.microbatches
        grads_zero = jax.tree.map(
            lambda p: jnp.zeros(p.shape, grad_dtype), params)

        if m == 1:
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch)
            grads = jax.tree.map(lambda g: g.astype(grad_dtype), grads)
        else:
            micro = _split_microbatches(batch, m, data_axes)

            def accum(carry, mb):
                g_acc, loss_acc = carry
                (loss, metrics), g = jax.value_and_grad(
                    loss_fn, has_aux=True)(params, mb)
                g_acc = jax.tree.map(
                    lambda a, b: a + b.astype(grad_dtype), g_acc, g)
                return (g_acc, loss_acc + loss), metrics

            (grads, loss_sum), metrics = jax.lax.scan(
                accum, (grads_zero, jnp.zeros(())), micro)
            grads = jax.tree.map(lambda g: g / m, grads)
            loss = loss_sum / m
            metrics = jax.tree.map(lambda x: x[-1], metrics)

        # schedule is indexed by the step being taken (1-based): the very
        # first update must not see lr=0 from the warmup ramp
        lr = schedule.warmup_cosine(
            opt_state.step + 1, base_lr=hyper.base_lr, warmup=hyper.warmup,
            total=hyper.total_steps)
        if isinstance(opt_state, AdafactorState):
            new_params, new_opt, opt_metrics = adafactor_update(
                params, grads, opt_state,
                lr=lr, b1=hyper.b1,
                weight_decay=hyper.weight_decay, clip_norm=hyper.clip_norm)
        else:
            new_params, new_opt, opt_metrics = adamw_update(
                params, grads, opt_state,
                lr=lr, b1=hyper.b1, b2=hyper.b2,
                weight_decay=hyper.weight_decay, clip_norm=hyper.clip_norm)
        out_metrics = {"loss": loss, "lr": lr, **metrics, **opt_metrics}
        return new_params, new_opt, out_metrics

    return train_step


def make_opt_init(hyper: TrainHyper):
    """Optimizer-state init fn selected by the hyper config."""
    return init_adafactor if hyper.optimizer == "adafactor" else init_adamw
