"""Mixture-of-Experts layer (granite-moe 32e top-8, grok-1 8e top-2,
granite-4.0-h 72e top-10 with a shared expert).

The layer is told which experts it holds: ``cfg.experts_held`` of them,
from ``cfg.moe_expert_offset``, this chip's share of an expert-parallel
layer (all of them unless the config says otherwise). The router keeps its
published width and routes every token over all ``cfg.num_experts``: the
top-k logits, in float32, gated by a softmax over those k. A token gets
each held expert it chose, weighted by its gate; no token is ever dropped,
at any batch size, and a token none of whose experts are held gets nothing
from the routed part here: what the absent experts would add is another
chip's share. A shared expert, where the config has one, runs on every
token.

Two layouts compute the same function, chosen by the config:

- grouped (``top_k < held``): each token's (token, expert) pairs that land
  on a held expert, sorted by expert, go through one ragged matmul per
  projection (``jax.lax.ragged_dot``). A token reaches at most
  ``min(top_k, held)`` held experts, so the rows are ``N * top_k``: the
  routed FLOPs of the top-k, with no capacity to drop at.
- dense (``top_k >= held``): every held expert runs on every token and its
  output is weighted by the token's gate for it (0 where the token did not
  choose it). That is no more rows than the grouped layout would take, and
  at decode the weights are read once for the whole batch. With
  ``cfg.use_pallas`` the experts run in the fused expert-FFN kernel (see
  kernels/moe_ffn), each over the whole token batch.

The named scopes ``ffn.router``, ``ffn.experts`` and ``ffn.shared`` mark
the three parts in the compiled program's op metadata.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.config import ModelConfig
from repro.models import layers as L


def init_moe(key, cfg: ModelConfig):
    """The router over all experts, the held experts' weights and the shared
    expert. Expert ``i`` of a projection is drawn from ``fold_in(k, i)``,
    so a share draws its own experts alone and the shares of one key tile
    the uncut layer."""
    dtype = L.dtype_of(cfg.param_dtype)
    fe, d, e = cfg.resolved_moe_d_ff, cfg.d_model, cfg.num_experts
    ids = cfg.moe_expert_offset + jnp.arange(cfg.experts_held)
    k1, k2, k3, k4 = jax.random.split(key, 4)

    def experts(k, fan_in, fan_out, std):
        return jax.vmap(lambda i: L.trunc_normal(
            jax.random.fold_in(k, i), (fan_in, fan_out), std, dtype))(ids)

    params = {
        "router": L.dense_init(k1, d, e, jnp.float32),
        "w_gate": experts(k2, d, fe, 1.0 / np.sqrt(d)),
        "w_up": experts(k3, d, fe, 1.0 / np.sqrt(d)),
        # scaled by the fan-in of the whole layer's down projection
        "w_down": experts(k4, fe, d, 1.0 / np.sqrt(e * fe)),
    }
    if cfg.shared_d_ff:
        params["shared"] = L.init_mlp(jax.random.fold_in(key, 4), d,
                                      cfg.shared_d_ff, dtype)
    return params


def router_topk(params, cfg: ModelConfig, x: jax.Array):
    """Top-k routing over all experts, gated by a softmax over the top-k
    logits (the softmax over all, renormalised over the top k, is the same
    function).

    x: (N, D) -> (assign (N,k) int32, gates (N,k) f32, probs (N,E) f32)
    """
    logits = jnp.einsum("nd,de->ne", x.astype(jnp.float32), params["router"])
    top, assign = jax.lax.top_k(logits, cfg.num_experts_per_tok)
    return (assign.astype(jnp.int32), jax.nn.softmax(top, axis=-1),
            jax.nn.softmax(logits, axis=-1))


def held_gates(assign, gates, cfg: ModelConfig) -> jax.Array:
    """(N, held) f32: each token's gate for each held expert, 0 where the
    token did not choose it."""
    ids = cfg.moe_expert_offset + jnp.arange(cfg.experts_held)
    hit = assign[:, :, None] == ids[None, None, :]            # (N, k, held)
    return jnp.sum(jnp.where(hit, gates[:, :, None], 0.0), axis=1)


def _dense(params, cfg: ModelConfig, x, assign, gates) -> jax.Array:
    """x (N, D) -> (N, D): every held expert's SwiGLU on every token,
    weighted by the token's gate for it."""
    comb = held_gates(assign, gates, cfg).astype(x.dtype)
    if cfg.use_pallas:
        from repro.kernels.moe_ffn import ops as moe_ops
        n, d = x.shape
        xin = jnp.broadcast_to(x[None, None], (1, cfg.experts_held, n, d))
        out = moe_ops.expert_ffn(xin, params["w_gate"], params["w_up"],
                                 params["w_down"])[0]
    else:
        gate = jnp.einsum("nd,edf->enf", x, params["w_gate"])
        up = jnp.einsum("nd,edf->enf", x, params["w_up"])
        out = jnp.einsum("enf,efd->end", jax.nn.silu(gate) * up,
                         params["w_down"])
    return jnp.einsum("end,ne->nd", out, comb)


def _grouped(params, cfg: ModelConfig, x, assign, gates) -> jax.Array:
    """x (N, D) -> (N, D): each token through the held experts it chose
    alone, its pairs sorted by expert into one ragged matmul a
    projection."""
    n, d = x.shape
    k, held = cfg.num_experts_per_tok, cfg.experts_held
    local = assign.reshape(-1) - cfg.moe_expert_offset           # (N*k,)
    is_held = (local >= 0) & (local < held)
    group = jnp.where(is_held, local, held)   # pairs held elsewhere: last
    order = jnp.argsort(group, stable=True)
    rows = n * min(k, held)   # every held pair, whatever the routing
    sizes = jnp.bincount(group, length=held + 1)[:held]
    xs = x[order[:rows] // k]
    h = (jax.nn.silu(jax.lax.ragged_dot(xs, params["w_gate"], sizes))
         * jax.lax.ragged_dot(xs, params["w_up"], sizes))
    out = jax.lax.ragged_dot(h, params["w_down"], sizes)          # (rows, D)
    # back to (token, choice) order; a pair held elsewhere reads 0
    pair = jnp.take(out, jnp.argsort(order), axis=0, mode="fill",
                    fill_value=0)
    pair = jnp.where(is_held[:, None], pair, 0).reshape(n, k, d)
    return jnp.einsum("nkd,nk->nd", pair, gates.astype(x.dtype))


def moe_ffn(params, cfg: ModelConfig, x: jax.Array
            ) -> Tuple[jax.Array, jax.Array]:
    """x (B, S, D) -> (output (B,S,D), load-balancing aux loss scalar)."""
    b, s, d = x.shape
    flat = x.reshape(b * s, d)
    with jax.named_scope("ffn.router"):
        assign, gates, probs = router_topk(params, cfg, flat)
        # switch-transformer load balancing over the router's experts
        frac_tokens = jnp.mean(jax.nn.one_hot(assign[:, 0], cfg.num_experts,
                                              dtype=jnp.float32), axis=0)
        aux = cfg.num_experts * jnp.sum(frac_tokens * jnp.mean(probs, 0))
    with jax.named_scope("ffn.experts"):
        experts = (_grouped if cfg.num_experts_per_tok < cfg.experts_held
                   else _dense)
        y = experts(params, cfg, flat, assign, gates)
    if "shared" in params:
        with jax.named_scope("ffn.shared"):
            y = y + L.mlp(params["shared"], flat)
    return y.reshape(b, s, d), aux
