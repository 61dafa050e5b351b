"""Unified decoder-only model covering dense / MoE / SSM / hybrid / VLM archs.

Layer layout
------------
Layers are organized into *periods* of a repeating pattern (gemma2 = [local,
global], gemma3 = [local x5, global], zamba2 = [mamba x N, shared-attn],
granite-4.0-h = ``cfg.layer_kinds``, e.g. [mamba x5, attn, mamba x4], each
slot a mixer followed by its own MoE), and the model scans over periods with
per-slot stacked parameters:

    params["layers"]["slot{i}"]  : pytree stacked along axis 0, (n_full, ...)
    params["tail"][j]            : unstacked params for the L % period tail
    params["shared"]             : single shared attn+mlp block (zamba2)

This keeps HLO size O(period) in depth (88-layer granite-34b compiles as one
scan), gives every slot a *static* attention window (no dynamic masks), and
lets local slots carry ring-buffer KV caches of window size while global
slots carry full-length caches — the memory trick that makes gemma-family
``long_500k`` decode feasible.

Caches mirror the layout: ``cache["slots"]["slot{i}"]`` stacked (n_full, ...)
consumed/produced as scan xs/ys, plus ``cache["tail"]`` and ``cache["shared"]``;
a period of mixed kinds keeps KV and SSM state side by side there.

Each residual branch joins the stream scaled by ``cfg.residual_multiplier``
(1 leaves it as it is). The named scopes ``mixer.mamba`` and
``mixer.attention`` mark the mixers in the compiled program's op metadata
(the FFN's parts are marked in ``moe.py``).
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.models import attention as A
from repro.models import layers as L
from repro.models import moe as M
from repro.models import ssm as S

Params = Dict[str, Any]
Cache = Dict[str, Any]


# --------------------------------------------------------------------------
# layout
# --------------------------------------------------------------------------


def layer_pattern(cfg: ModelConfig) -> List[str]:
    """Slot kinds for one period: 'attn_local' | 'attn_global' | 'mamba'."""
    if cfg.layer_kinds:
        return list(cfg.layer_kinds)
    if cfg.family == "ssm":
        return ["mamba"]
    if cfg.family == "hybrid":
        every = max(cfg.hybrid_attn_every, 1)
        return ["mamba"] * every  # shared attn applied at period end
    if cfg.attn_pattern == "local_global":
        n_local, n_global = cfg.local_global_ratio
        return ["attn_local"] * n_local + ["attn_global"] * n_global
    return ["attn_global"]


def layout(cfg: ModelConfig) -> Tuple[List[str], int, List[str]]:
    """Returns (pattern, n_full_periods, tail_kinds)."""
    pattern = layer_pattern(cfg)
    p = len(pattern)
    n_full = cfg.num_layers // p
    tail = [pattern[i] for i in range(cfg.num_layers - n_full * p)]
    return pattern, n_full, tail


def slot_window(cfg: ModelConfig, kind: str) -> Optional[int]:
    return cfg.local_window if kind == "attn_local" else None


# --------------------------------------------------------------------------
# per-layer init / apply
# --------------------------------------------------------------------------


def _init_ffn(key, cfg: ModelConfig) -> Params:
    p: Params = {"norm_mlp": L.init_rmsnorm(cfg.d_model)}
    if cfg.is_moe:
        p["moe"] = M.init_moe(key, cfg)
    else:
        p["mlp"] = L.init_mlp(key, cfg.d_model, cfg.d_ff,
                              L.dtype_of(cfg.param_dtype))
    return p


def _init_layer(key, cfg: ModelConfig, kind: str) -> Params:
    if kind == "mamba":
        p = {"norm": L.init_rmsnorm(cfg.d_model),
             "mamba": S.init_mamba(key, cfg)}
        if cfg.layer_kinds:  # every slot of such a period has its FFN
            p.update(_init_ffn(jax.random.fold_in(key, 1), cfg))
        return p
    k1, k2 = jax.random.split(key)
    p: Params = {
        "norm_attn": L.init_rmsnorm(cfg.d_model),
        "attn": A.init_attention(k1, cfg),
        **_init_ffn(k2, cfg),
    }
    if cfg.post_norms:
        p["post_attn"] = L.init_rmsnorm(cfg.d_model)
        p["post_mlp"] = L.init_rmsnorm(cfg.d_model)
    return p


def _init_shared_block(key, cfg: ModelConfig) -> Params:
    """zamba2 shared transformer block (attention + MLP, one param set)."""
    k1, k2 = jax.random.split(key)
    return {
        "norm_attn": L.init_rmsnorm(cfg.d_model),
        "attn": A.init_attention(k1, cfg),
        "norm_mlp": L.init_rmsnorm(cfg.d_model),
        "mlp": L.init_mlp(k2, cfg.d_model, cfg.d_ff, L.dtype_of(cfg.param_dtype)),
    }


def _join(cfg: ModelConfig, x, h):
    """The stream after a residual branch ``h``."""
    if cfg.residual_multiplier != 1.0:
        # in float32, rounded once: bf16(0.22) would bias every branch
        h = (h.astype(jnp.float32) * cfg.residual_multiplier).astype(h.dtype)
    return x + h


def _apply_ffn(p: Params, cfg: ModelConfig, x):
    """The FFN branch and its residual. Returns (x, moe aux loss)."""
    aux = jnp.zeros((), jnp.float32)
    normed = L.rmsnorm(p["norm_mlp"], x, cfg.norm_eps)
    if cfg.is_moe:
        h, aux = M.moe_ffn(p["moe"], cfg, normed)
    else:
        h = L.mlp(p["mlp"], normed)
    if cfg.post_norms:
        h = L.rmsnorm(p["post_mlp"], h, cfg.norm_eps)
    return _join(cfg, x, h), aux


def _apply_attn_layer_full(p: Params, cfg: ModelConfig, x, positions, window):
    with jax.named_scope("mixer.attention"):
        h, kv = A.attn_prefill(p["attn"], cfg,
                               L.rmsnorm(p["norm_attn"], x, cfg.norm_eps),
                               positions, window=window)
    if cfg.post_norms:
        h = L.rmsnorm(p["post_attn"], h, cfg.norm_eps)
    x, aux = _apply_ffn(p, cfg, _join(cfg, x, h))
    return x, aux, kv


def _apply_mamba_layer_full(p: Params, cfg: ModelConfig, x,
                            initial: Optional[S.SSMState] = None):
    """Returns (x, moe aux loss, final SSM state)."""
    with jax.named_scope("mixer.mamba"):
        h, state = S.mamba_prefill(p["mamba"], cfg,
                                   L.rmsnorm(p["norm"], x, cfg.norm_eps),
                                   initial)
    x = _join(cfg, x, h)
    aux = jnp.zeros((), jnp.float32)
    if cfg.layer_kinds:
        x, aux = _apply_ffn(p, cfg, x)
    return x, aux, state


def _apply_attn_layer_decode(p: Params, cfg: ModelConfig, x, lc: Cache,
                             cache_len, window, ring: bool):
    with jax.named_scope("mixer.attention"):
        h, (ck, cv) = A.attn_decode(
            p["attn"], cfg, L.rmsnorm(p["norm_attn"], x, cfg.norm_eps),
            lc["k"], lc["v"], cache_len, window=window, ring=ring)
    if cfg.post_norms:
        h = L.rmsnorm(p["post_attn"], h, cfg.norm_eps)
    x, _ = _apply_ffn(p, cfg, _join(cfg, x, h))
    return x, {"k": ck, "v": cv}


def _apply_mamba_layer_decode(p: Params, cfg: ModelConfig, x, state: S.SSMState):
    with jax.named_scope("mixer.mamba"):
        h, new_state = S.mamba_decode(p["mamba"], cfg,
                                      L.rmsnorm(p["norm"], x, cfg.norm_eps),
                                      state)
    x = _join(cfg, x, h)
    if cfg.layer_kinds:
        x, _ = _apply_ffn(p, cfg, x)
    return x, new_state


# --------------------------------------------------------------------------
# cache construction
# --------------------------------------------------------------------------


def _empty_layer_cache(cfg: ModelConfig, kind: str, batch: int, max_len: int):
    hd = cfg.resolved_head_dim
    dt = L.dtype_of(cfg.dtype)
    if kind == "mamba":
        return S.init_ssm_state(cfg, batch)
    size = cfg.local_window if kind == "attn_local" else max_len
    size = min(size, max_len)
    return {"k": jnp.zeros((batch, size, cfg.num_kv_heads, hd), dt),
            "v": jnp.zeros((batch, size, cfg.num_kv_heads, hd), dt)}


def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> Cache:
    pattern, n_full, tail = layout(cfg)

    def stack(make):
        return jax.tree.map(lambda *xs: jnp.stack(xs), *[make() for _ in range(n_full)]) \
            if n_full > 0 else None

    slots = {}
    for i, kind in enumerate(pattern):
        if n_full > 0:
            one = _empty_layer_cache(cfg, kind, batch, max_len)
            slots[f"slot{i}"] = jax.tree.map(
                lambda x: jnp.broadcast_to(x[None], (n_full,) + x.shape), one)
    cache: Cache = {
        "len": jnp.zeros((), jnp.int32),
        "slots": slots,
        "tail": [_empty_layer_cache(cfg, kind, batch, max_len) for kind in tail],
    }
    if cfg.family == "hybrid" and n_full > 0:
        one = _empty_layer_cache(cfg, "attn_global", batch, max_len)
        cache["shared"] = jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (n_full,) + x.shape), one)
    return cache


# --------------------------------------------------------------------------
# model init
# --------------------------------------------------------------------------


def init_params(key, cfg: ModelConfig) -> Params:
    pattern, n_full, tail = layout(cfg)
    keys = jax.random.split(key, 8)
    params: Params = {
        "embed": L.init_embedding(keys[0], cfg),
        "final_norm": L.init_rmsnorm(cfg.d_model),
    }
    layers = {}
    for i, kind in enumerate(pattern):
        if n_full == 0:
            continue
        slot_keys = jax.random.split(jax.random.fold_in(keys[1], i), n_full)
        layers[f"slot{i}"] = jax.vmap(
            lambda k, kind=kind: _init_layer(k, cfg, kind))(slot_keys)
    params["layers"] = layers
    params["tail"] = [
        _init_layer(jax.random.fold_in(keys[2], j), cfg, kind)
        for j, kind in enumerate(tail)
    ]
    if cfg.family == "hybrid":
        params["shared"] = _init_shared_block(keys[3], cfg)
    if cfg.family == "vlm" and cfg.vit_dim:
        params["patch_proj"] = L.dense_init(keys[4], cfg.vit_dim, cfg.d_model,
                                            L.dtype_of(cfg.param_dtype))
    return params


# --------------------------------------------------------------------------
# embedding helpers
# --------------------------------------------------------------------------


def _embed_inputs(params, cfg: ModelConfig, tokens: Optional[jax.Array],
                  patch_embeds: Optional[jax.Array] = None) -> jax.Array:
    parts = []
    if patch_embeds is not None:
        pe = patch_embeds.astype(L.dtype_of(cfg.dtype))
        if "patch_proj" in params:
            pe = jnp.einsum("bpe,ed->bpd", pe, params["patch_proj"])
        parts.append(pe)
    if tokens is not None:
        parts.append(L.embed(params["embed"], cfg, tokens))
    x = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)
    if cfg.scale_embeddings:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)
    if cfg.embedding_multiplier != 1.0:
        x = x * cfg.embedding_multiplier
    return x


# --------------------------------------------------------------------------
# full forward (train path)
# --------------------------------------------------------------------------


def forward(
    params: Params,
    cfg: ModelConfig,
    tokens: Optional[jax.Array],
    *,
    patch_embeds: Optional[jax.Array] = None,
    return_hidden: bool = False,
):
    """Full-sequence forward. Returns (logits_or_hidden, moe_aux_loss)."""
    pattern, n_full, tail = layout(cfg)
    x = _embed_inputs(params, cfg, tokens, patch_embeds)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))

    def period_body(carry, slot_params):
        x, aux = carry
        for i, kind in enumerate(pattern):
            p = slot_params[f"slot{i}"]
            if kind == "mamba":
                x, a, _ = _apply_mamba_layer_full(p, cfg, x)
            else:
                x, a, _ = _apply_attn_layer_full(p, cfg, x, positions,
                                                 slot_window(cfg, kind))
            aux = aux + a
        if cfg.family == "hybrid":
            x, a, _ = _apply_attn_layer_full(params["shared"], cfg, x,
                                             positions, None)
            aux = aux + a
        return (x, aux), None

    aux = jnp.zeros((), jnp.float32)
    if n_full > 0:
        (x, aux), _ = jax.lax.scan(period_body, (x, aux), params["layers"],
                                   length=n_full)
    for j, kind in enumerate(tail):
        p = params["tail"][j]
        if kind == "mamba":
            x, a, _ = _apply_mamba_layer_full(p, cfg, x)
        else:
            x, a, _ = _apply_attn_layer_full(p, cfg, x, positions,
                                             slot_window(cfg, kind))
        aux = aux + a

    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    if return_hidden:
        return x, aux
    return L.unembed(params["embed"], cfg, x), aux


# --------------------------------------------------------------------------
# prefill (build cache) — layer-by-layer full attention, caches seeded
# --------------------------------------------------------------------------


def _seed_attn_cache(cfg: ModelConfig, kind: str, k, v, max_len: int):
    """Pack prefill K/V (B,S,Kh,Hd) into a decode cache buffer."""
    b, s, kh, hd = k.shape
    if kind == "attn_local":
        size = min(cfg.local_window, max_len)
        idx = jnp.arange(size)
        # latest position p <= s-1 with p % size == idx
        pos = (s - 1) - ((s - 1 - idx) % size)
        valid = pos >= 0
        pos_c = jnp.clip(pos, 0, s - 1)
        ck = jnp.where(valid[None, :, None, None], k[:, pos_c], 0)
        cv = jnp.where(valid[None, :, None, None], v[:, pos_c], 0)
    else:
        pad = max_len - s
        ck = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        cv = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    return {"k": ck, "v": cv}


def prefill(
    params: Params,
    cfg: ModelConfig,
    tokens: Optional[jax.Array],
    max_len: int,
    *,
    patch_embeds: Optional[jax.Array] = None,
):
    """Run the prompt through the model, returning (last-position logits,
    populated decode cache)."""
    pattern, n_full, tail = layout(cfg)
    x = _embed_inputs(params, cfg, tokens, patch_embeds)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))

    def period_body(x, slot_params):
        new_caches = {}
        for i, kind in enumerate(pattern):
            p = slot_params[f"slot{i}"]
            if kind == "mamba":
                x, _, st = _apply_mamba_layer_full(p, cfg, x)
                new_caches[f"slot{i}"] = st
            else:
                x, _, (k, v) = _apply_attn_layer_full(p, cfg, x, positions,
                                                      slot_window(cfg, kind))
                new_caches[f"slot{i}"] = _seed_attn_cache(cfg, kind, k, v, max_len)
        if cfg.family == "hybrid":
            x, _, (k, v) = _apply_attn_layer_full(params["shared"], cfg, x,
                                                  positions, None)
            new_caches["shared"] = _seed_attn_cache(cfg, "attn_global", k, v,
                                                    max_len)
        return x, new_caches

    slot_caches: Dict[str, Any] = {}
    if n_full > 0:
        x, stacked = jax.lax.scan(period_body, x, params["layers"], length=n_full)
        slot_caches = stacked

    tail_caches = []
    for j, kind in enumerate(tail):
        p = params["tail"][j]
        if kind == "mamba":
            x, _, st = _apply_mamba_layer_full(p, cfg, x)
            tail_caches.append(st)
        else:
            x, _, (k, v) = _apply_attn_layer_full(p, cfg, x, positions,
                                                  slot_window(cfg, kind))
            tail_caches.append(_seed_attn_cache(cfg, kind, k, v, max_len))

    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = L.unembed(params["embed"], cfg, x[:, -1:, :])

    cache: Cache = {
        "len": jnp.asarray(s, jnp.int32),
        "slots": {k: slot_caches[k] for k in slot_caches if k != "shared"},
        "tail": tail_caches,
    }
    if cfg.family == "hybrid" and n_full > 0:
        cache["shared"] = slot_caches["shared"]
    return logits, cache


# --------------------------------------------------------------------------
# decode step (serving path)
# --------------------------------------------------------------------------


def decode_step(
    params: Params,
    cfg: ModelConfig,
    token: jax.Array,  # (B, 1) int32
    cache: Cache,
):
    """One autoregressive step. Returns (logits (B,1,V), updated cache)."""
    pattern, n_full, tail = layout(cfg)
    x = _embed_inputs(params, cfg, token)
    cache_len = cache["len"]

    def period_body(x, slot_params, slot_caches):
        new_caches = {}
        for i, kind in enumerate(pattern):
            p = slot_params[f"slot{i}"]
            lc = slot_caches[f"slot{i}"]
            if kind == "mamba":
                x, st = _apply_mamba_layer_decode(p, cfg, x, lc)
                new_caches[f"slot{i}"] = st
            else:
                ring = kind == "attn_local" and lc["k"].shape[1] == cfg.local_window
                x, nc = _apply_attn_layer_decode(
                    p, cfg, x, lc, cache_len, slot_window(cfg, kind), ring)
                new_caches[f"slot{i}"] = nc
        if cfg.family == "hybrid":
            x, nc = _apply_attn_layer_decode(
                params["shared"], cfg, x, slot_caches["shared"], cache_len,
                None, False)
            new_caches["shared"] = nc
        return x, new_caches

    # The stacked caches ride in the carry and each period's slice is
    # written back in place: as the scan's ys they would be a fresh buffer,
    # copied whole into the donated cache after the loop.
    def scan_body(carry, slot_params):
        x, i, stacked = carry
        here = jax.tree.map(
            lambda leaf: jax.lax.dynamic_index_in_dim(leaf, i, keepdims=False),
            stacked)
        x, new_caches = period_body(x, slot_params, here)
        stacked = jax.tree.map(
            lambda leaf, new: jax.lax.dynamic_update_index_in_dim(
                leaf, new.astype(leaf.dtype), i, 0),
            stacked, new_caches)
        return (x, i + 1, stacked), None

    if n_full > 0:
        scan_caches = dict(cache["slots"])
        if cfg.family == "hybrid":
            scan_caches["shared"] = cache["shared"]
        (x, _, new_stacked), _ = jax.lax.scan(
            scan_body, (x, jnp.zeros((), jnp.int32), scan_caches),
            params["layers"], length=n_full)
        new_slots = {k: v for k, v in new_stacked.items() if k != "shared"}
    else:
        new_slots, new_stacked = {}, {}

    new_tail = []
    for j, kind in enumerate(tail):
        p = params["tail"][j]
        lc = cache["tail"][j]
        if kind == "mamba":
            x, st = _apply_mamba_layer_decode(p, cfg, x, lc)
            new_tail.append(st)
        else:
            ring = kind == "attn_local" and lc["k"].shape[1] == cfg.local_window
            x, nc = _apply_attn_layer_decode(
                p, cfg, x, lc, cache_len, slot_window(cfg, kind), ring)
            new_tail.append(nc)

    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = L.unembed(params["embed"], cfg, x)

    new_cache: Cache = {
        "len": cache_len + 1,
        "slots": new_slots,
        "tail": new_tail,
    }
    if cfg.family == "hybrid" and n_full > 0:
        new_cache["shared"] = new_stacked["shared"]
    return logits, new_cache
