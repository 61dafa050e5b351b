"""Encoder-decoder backbone (whisper-medium).

The audio conv frontend is a STUB per the assignment: ``frames`` inputs are
precomputed frame embeddings of shape (B, T_enc, d_model) — what whisper's
two conv layers + sinusoidal embedding would produce. The transformer
backbone (24 enc + 24 dec layers, d_model 1024, 16 heads, d_ff 4096, GELU
MLPs) is implemented fully.

Adaptations from the original (documented in DESIGN.md): RMSNorm instead of
LayerNorm-with-bias, RoPE instead of learned positions. Neither changes the
systems behaviour (shapes, FLOPs, collectives) this framework studies.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.models import attention as A
from repro.models import layers as L

Params = Dict[str, Any]
Cache = Dict[str, Any]


# --------------------------------------------------------------------------
# init
# --------------------------------------------------------------------------


def _init_enc_layer(key, cfg: ModelConfig) -> Params:
    k1, k2 = jax.random.split(key)
    return {
        "norm_attn": L.init_rmsnorm(cfg.d_model),
        "attn": A.init_attention(k1, cfg),
        "norm_mlp": L.init_rmsnorm(cfg.d_model),
        "mlp": L.init_gelu_mlp(k2, cfg.d_model, cfg.d_ff, L.dtype_of(cfg.param_dtype)),
    }


def _init_dec_layer(key, cfg: ModelConfig) -> Params:
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "norm_self": L.init_rmsnorm(cfg.d_model),
        "attn_self": A.init_attention(k1, cfg),
        "norm_cross": L.init_rmsnorm(cfg.d_model),
        "attn_cross": A.init_attention(k2, cfg, cross=True),
        "norm_mlp": L.init_rmsnorm(cfg.d_model),
        "mlp": L.init_gelu_mlp(k3, cfg.d_model, cfg.d_ff, L.dtype_of(cfg.param_dtype)),
    }


def init_params(key, cfg: ModelConfig) -> Params:
    k_emb, k_enc, k_dec = jax.random.split(key, 3)
    enc_keys = jax.random.split(k_enc, cfg.encoder_layers)
    dec_keys = jax.random.split(k_dec, cfg.num_layers)
    return {
        "embed": L.init_embedding(k_emb, cfg),
        "enc_layers": jax.vmap(lambda k: _init_enc_layer(k, cfg))(enc_keys),
        "enc_norm": L.init_rmsnorm(cfg.d_model),
        "dec_layers": jax.vmap(lambda k: _init_dec_layer(k, cfg))(dec_keys),
        "final_norm": L.init_rmsnorm(cfg.d_model),
    }


# --------------------------------------------------------------------------
# encoder
# --------------------------------------------------------------------------


def encode(params: Params, cfg: ModelConfig, frames: jax.Array) -> jax.Array:
    """frames: (B, T_enc, d_model) stub embeddings -> encoder states."""
    x = frames.astype(L.dtype_of(cfg.dtype))
    b, t, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32)[None], (b, t))

    def body(x, p):
        h, _ = A.attn_prefill(p["attn"], cfg,
                              L.rmsnorm(p["norm_attn"], x, cfg.norm_eps),
                              positions, causal=False)
        x = x + h
        x = x + L.gelu_mlp(p["mlp"], L.rmsnorm(p["norm_mlp"], x, cfg.norm_eps))
        return x, None

    x, _ = jax.lax.scan(body, x, params["enc_layers"], length=cfg.encoder_layers)
    return L.rmsnorm(params["enc_norm"], x, cfg.norm_eps)


# --------------------------------------------------------------------------
# decoder — full forward (train)
# --------------------------------------------------------------------------


def forward(
    params: Params,
    cfg: ModelConfig,
    frames: jax.Array,
    tokens: jax.Array,
    *,
    return_hidden: bool = False,
):
    """Teacher-forced decode over the full target sequence."""
    enc_out = encode(params, cfg, frames)
    x = L.embed(params["embed"], cfg, tokens)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))

    def body(x, p):
        h, _ = A.attn_prefill(p["attn_self"], cfg,
                              L.rmsnorm(p["norm_self"], x, cfg.norm_eps),
                              positions, causal=True)
        x = x + h
        h, _ = A.attn_prefill(p["attn_cross"], cfg,
                              L.rmsnorm(p["norm_cross"], x, cfg.norm_eps),
                              positions, kv_x=enc_out, causal=False)
        x = x + h
        x = x + L.gelu_mlp(p["mlp"], L.rmsnorm(p["norm_mlp"], x, cfg.norm_eps))
        return x, None

    x, _ = jax.lax.scan(body, x, params["dec_layers"], length=cfg.num_layers)
    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    aux = jnp.zeros((), jnp.float32)
    if return_hidden:
        return x, aux
    return L.unembed(params["embed"], cfg, x), aux


# --------------------------------------------------------------------------
# serving: prefill + decode_step
# --------------------------------------------------------------------------


def prefill(
    params: Params,
    cfg: ModelConfig,
    frames: jax.Array,
    tokens: jax.Array,
    max_len: int,
):
    enc_out = encode(params, cfg, frames)
    x = L.embed(params["embed"], cfg, tokens)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))

    def body(x, p):
        h, (k, v) = A.attn_prefill(p["attn_self"], cfg,
                                   L.rmsnorm(p["norm_self"], x, cfg.norm_eps),
                                   positions, causal=True)
        x = x + h
        ck, cv = A.precompute_cross_kv(p["attn_cross"], cfg, enc_out)
        h, _ = A.attn_prefill(p["attn_cross"], cfg,
                              L.rmsnorm(p["norm_cross"], x, cfg.norm_eps),
                              positions, kv_x=enc_out, causal=False)
        x = x + h
        x = x + L.gelu_mlp(p["mlp"], L.rmsnorm(p["norm_mlp"], x, cfg.norm_eps))
        pad = max_len - s
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        return x, {"self": {"k": k, "v": v}, "cross": {"k": ck, "v": cv}}

    x, caches = jax.lax.scan(body, x, params["dec_layers"], length=cfg.num_layers)
    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = L.unembed(params["embed"], cfg, x[:, -1:, :])
    cache: Cache = {"len": jnp.asarray(s, jnp.int32),
                    "self": caches["self"], "cross": caches["cross"]}
    return logits, cache


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               enc_len: Optional[int] = None) -> Cache:
    """Decode cache with (optionally zeroed) cross-attention K/V."""
    hd = cfg.resolved_head_dim
    dt = L.dtype_of(cfg.dtype)
    tenc = enc_len or cfg.encoder_seq_len
    lcount = cfg.num_layers
    return {
        "len": jnp.zeros((), jnp.int32),
        "self": {"k": jnp.zeros((lcount, batch, max_len, cfg.num_kv_heads, hd), dt),
                 "v": jnp.zeros((lcount, batch, max_len, cfg.num_kv_heads, hd), dt)},
        "cross": {"k": jnp.zeros((lcount, batch, tenc, cfg.num_kv_heads, hd), dt),
                  "v": jnp.zeros((lcount, batch, tenc, cfg.num_kv_heads, hd), dt)},
    }


def decode_step(
    params: Params,
    cfg: ModelConfig,
    token: jax.Array,  # (B, 1)
    cache: Cache,
):
    x = L.embed(params["embed"], cfg, token)
    cache_len = cache["len"]

    def body(x, xs):
        p, sc, cc = xs
        h, (ck, cv) = A.attn_decode(
            p["attn_self"], cfg, L.rmsnorm(p["norm_self"], x, cfg.norm_eps),
            sc["k"], sc["v"], cache_len)
        x = x + h
        h = A.attn_cross_decode(
            p["attn_cross"], cfg, L.rmsnorm(p["norm_cross"], x, cfg.norm_eps),
            cc["k"], cc["v"])
        x = x + h
        x = x + L.gelu_mlp(p["mlp"], L.rmsnorm(p["norm_mlp"], x, cfg.norm_eps))
        return x, {"k": ck, "v": cv}

    x, new_self = jax.lax.scan(
        body, x, (params["dec_layers"], cache["self"], cache["cross"]),
        length=cfg.num_layers)
    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = L.unembed(params["embed"], cfg, x)
    return logits, {"len": cache_len + 1, "self": new_self,
                    "cross": cache["cross"]}
