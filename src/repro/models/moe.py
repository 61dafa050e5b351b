"""Mixture-of-Experts layer (granite-moe 32e top-8, grok-1 8e top-2).

Implementation is the grouped dense-dispatch ("einsum MoE") formulation:
tokens are split into groups, and within each group a (S_g, E, C) one-hot
dispatch tensor routes tokens to per-expert capacity slots. This formulation

- keeps every shape static (jit/scan friendly),
- shards naturally: token/group axes follow the batch ("data") sharding and
  the expert axis E shards over the "model" mesh axis (expert parallelism),
- has dispatch-einsum overhead O(N * G * k * cf * D) — <1% of expert-FFN
  FLOPs at the default group size.

An alternative fused expert-FFN Pallas kernel operates on the dispatched
(E, C, D) layout (see kernels/moe_ffn) and is selected via ``cfg.use_pallas``.
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.models import layers as L

DEFAULT_GROUP = 512
DEFAULT_CAPACITY_FACTOR = 1.25


def init_moe(key, cfg: ModelConfig):
    dtype = L.dtype_of(cfg.param_dtype)
    fe = cfg.resolved_moe_d_ff
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "router": L.dense_init(k1, cfg.d_model, cfg.num_experts, jnp.float32),
        "w_gate": (L.dense_init(k2, cfg.d_model, cfg.num_experts * fe, dtype)
                   .reshape(cfg.d_model, cfg.num_experts, fe).transpose(1, 0, 2)),
        "w_up": (L.dense_init(k3, cfg.d_model, cfg.num_experts * fe, dtype)
                 .reshape(cfg.d_model, cfg.num_experts, fe).transpose(1, 0, 2)),
        "w_down": (L.dense_init(k4, fe * cfg.num_experts, cfg.d_model, dtype)
                   .reshape(cfg.num_experts, fe, cfg.d_model)),
    }


def router_topk(params, cfg: ModelConfig, x: jax.Array):
    """Top-k routing with softmax-renormalized gates.

    x: (N, D) -> (assign (N,k) int32, gates (N,k) f32, probs (N,E) f32)
    """
    logits = jnp.einsum("nd,de->ne", x.astype(jnp.float32), params["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, assign = jax.lax.top_k(probs, cfg.num_experts_per_tok)
    gates = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)
    return assign.astype(jnp.int32), gates, probs


def _dispatch_combine(assign, gates, num_experts: int, capacity: int, dtype):
    """Build (S, E, C) dispatch/combine tensors for one token group.

    Priority is slot-major (all top-1 choices claim capacity before top-2),
    matching standard switch-transformer dispatch semantics.
    """
    s, k = assign.shape
    oh = jax.nn.one_hot(assign, num_experts, dtype=jnp.int32)  # (S,k,E)
    oh_prio = jnp.transpose(oh, (1, 0, 2)).reshape(k * s, num_experts)
    pos = jnp.cumsum(oh_prio, axis=0) - oh_prio  # position within each expert
    pos = pos.reshape(k, s, num_experts).transpose(1, 0, 2)  # (S,k,E)
    pos_sel = jnp.sum(pos * oh, axis=-1)  # (S,k)
    keep = (pos_sel < capacity).astype(dtype)
    slot_oh = jax.nn.one_hot(pos_sel, capacity, dtype=dtype)  # (S,k,C)
    disp = jnp.einsum("ske,skc,sk->sec", oh.astype(dtype), slot_oh, keep)
    comb = jnp.einsum("ske,skc,sk->sec", oh.astype(dtype), slot_oh,
                      keep * gates.astype(dtype))
    return disp, comb


def expert_capacity(tokens_per_group: int, cfg: ModelConfig,
                    capacity_factor: float = 0.0) -> int:
    cf = capacity_factor or cfg.moe_capacity_factor
    c = math.ceil(tokens_per_group * cfg.num_experts_per_tok
                  * cf / cfg.num_experts)
    return max(4, min(c, tokens_per_group))


def _expert_ffn(params, xin: jax.Array, cfg: ModelConfig) -> jax.Array:
    """xin: (G, E, C, D) -> (G, E, C, D). SwiGLU per expert."""
    if cfg.use_pallas:
        from repro.kernels.moe_ffn import ops as moe_ops
        g, e, c, d = xin.shape
        out = moe_ops.expert_ffn(
            xin.reshape(g * e, c, d).reshape(g, e, c, d),  # no-op, kept for clarity
            params["w_gate"], params["w_up"], params["w_down"],
        )
        return out
    gate = jnp.einsum("gecd,edf->gecf", xin, params["w_gate"])
    up = jnp.einsum("gecd,edf->gecf", xin, params["w_up"])
    return jnp.einsum("gecf,efd->gecd", jax.nn.silu(gate) * up, params["w_down"])


def moe_ffn(
    params,
    cfg: ModelConfig,
    x: jax.Array,  # (B, S, D)
    *,
    group_size: Optional[int] = None,
    capacity_factor: float = 0.0,
) -> Tuple[jax.Array, jax.Array]:
    """Returns (output (B,S,D), load-balancing aux loss scalar)."""
    b, s, d = x.shape
    n = b * s
    gs = min(group_size or cfg.moe_group_size, n)
    # pad token count to a multiple of the group size
    n_pad = math.ceil(n / gs) * gs
    flat = x.reshape(n, d)
    if n_pad != n:
        flat = jnp.pad(flat, ((0, n_pad - n), (0, 0)))
    ng = n_pad // gs

    assign, gates, probs = router_topk(params, cfg, flat)

    # aux loss on unpadded tokens (switch-transformer load balancing)
    tok_oh = jax.nn.one_hot(assign[:n, 0], cfg.num_experts, dtype=jnp.float32)
    frac_tokens = jnp.mean(tok_oh, axis=0)
    frac_probs = jnp.mean(probs[:n], axis=0)
    aux = cfg.num_experts * jnp.sum(frac_tokens * frac_probs)

    cap = expert_capacity(gs, cfg, capacity_factor)

    assign_g = assign.reshape(ng, gs, -1)
    gates_g = gates.reshape(ng, gs, -1)
    disp, comb = jax.vmap(
        lambda a, g: _dispatch_combine(a, g, cfg.num_experts, cap, x.dtype)
    )(assign_g, gates_g)

    xg = flat.reshape(ng, gs, d)
    xin = jnp.einsum("gsec,gsd->gecd", disp, xg)
    xout = _expert_ffn(params, xin, cfg)
    yg = jnp.einsum("gsec,gecd->gsd", comb, xout)
    y = yg.reshape(n_pad, d)[:n].reshape(b, s, d)
    return y, aux
