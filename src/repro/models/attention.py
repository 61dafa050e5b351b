"""Grouped-query attention with RoPE (or none), sliding windows, softcap and
KV caches.

One attention implementation serves every assigned architecture:

- ``global``/``local`` layers differ only by a dynamic ``window`` scalar, so a
  single scan body covers gemma2/gemma3 interleaved patterns.
- prefill/train path computes full (masked) attention; optionally routed
  through the Pallas flash-attention kernel (``cfg.use_pallas``).
- decode path attends a single query position against a KV cache; local
  layers may use a ring-buffer cache of ``window`` size (see serving/kv_cache).
- cross-attention (whisper decoder) reuses the same block with ``kv_x`` set
  and RoPE disabled on keys.
- ``cfg.position_embedding == "nope"`` (granite-4.0-h) leaves q and k
  unrotated on every path, and ``cfg.resolved_attn_scale`` scales the
  scores on every path.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.models import layers as L

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


# --------------------------------------------------------------------------
# params
# --------------------------------------------------------------------------


def init_attention(key, cfg: ModelConfig, cross: bool = False):
    dtype = L.dtype_of(cfg.param_dtype)
    hd = cfg.resolved_head_dim
    k1, k2, k3, k4 = jax.random.split(key, 4)
    params = {
        "wq": L.dense_init(k1, cfg.d_model, cfg.num_heads * hd, dtype),
        "wk": L.dense_init(k2, cfg.d_model, cfg.num_kv_heads * hd, dtype),
        "wv": L.dense_init(k3, cfg.d_model, cfg.num_kv_heads * hd, dtype),
        "wo": L.dense_init(k4, cfg.num_heads * hd, cfg.d_model, dtype),
    }
    if cfg.qk_norm and not cross:
        params["q_norm"] = L.init_rmsnorm(hd)
        params["k_norm"] = L.init_rmsnorm(hd)
    return params


# --------------------------------------------------------------------------
# core masked attention (pure jnp reference path)
# --------------------------------------------------------------------------


def _expand_kv(k: jax.Array, num_heads: int) -> jax.Array:
    """(B,S,K,Hd) -> (B,S,H,Hd) by repeating each kv head G=H/K times."""
    b, s, kv, hd = k.shape
    g = num_heads // kv
    if g == 1:
        return k
    return jnp.repeat(k, g, axis=2)


def mask_logits(
    scores: jax.Array,
    q_pos: jax.Array,
    k_pos: jax.Array,
    *,
    causal: bool,
    window,
) -> jax.Array:
    """scores: (B,H,Q,K). window: None/0 = unlimited; else attend iff
    0 <= q_pos - k_pos < window (local sliding window)."""
    dq = q_pos[:, :, None] if q_pos.ndim == 2 else q_pos[None, :, None]
    dk = k_pos[:, None, :] if k_pos.ndim == 2 else k_pos[None, None, :]
    delta = dq - dk  # (B?,Q,K)
    ok = jnp.ones_like(delta, dtype=bool)
    if causal:
        ok = ok & (delta >= 0)
    if window is not None:
        w = jnp.asarray(window, delta.dtype)
        ok = ok & jnp.where(w > 0, delta < w, True)
    return jnp.where(ok[:, None, :, :], scores, NEG_INF)


def attend(
    q: jax.Array,  # (B,Q,H,Hd)
    k: jax.Array,  # (B,K,Kh,Hd)
    v: jax.Array,  # (B,K,Kh,Hd)
    *,
    q_pos: jax.Array,
    k_pos: jax.Array,
    causal: bool = True,
    window=None,
    attn_softcap: float = 0.0,
    kv_valid: Optional[jax.Array] = None,  # (B,K) bool — cache validity
    scale: Optional[float] = None,  # None -> head_dim ** -0.5
) -> jax.Array:
    """Reference masked attention. Returns (B,Q,H,Hd)."""
    num_heads = q.shape[2]
    k = _expand_kv(k, num_heads)
    v = _expand_kv(v, num_heads)
    if scale is None:
        scale = q.shape[-1] ** -0.5
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    scores = L.softcap(scores, attn_softcap)
    scores = mask_logits(scores, q_pos, k_pos, causal=causal, window=window)
    if kv_valid is not None:
        scores = jnp.where(kv_valid[:, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


# --------------------------------------------------------------------------
# attention block (projections + rope + attend + output proj)
# --------------------------------------------------------------------------


def project_qkv(params, cfg: ModelConfig, x: jax.Array, kv_x: Optional[jax.Array] = None):
    hd = cfg.resolved_head_dim
    src = x if kv_x is None else kv_x
    q = jnp.einsum("...d,de->...e", x, params["wq"])
    k = jnp.einsum("...d,de->...e", src, params["wk"])
    v = jnp.einsum("...d,de->...e", src, params["wv"])
    q = q.reshape(*q.shape[:-1], cfg.num_heads, hd)
    k = k.reshape(*k.shape[:-1], cfg.num_kv_heads, hd)
    v = v.reshape(*v.shape[:-1], cfg.num_kv_heads, hd)
    if cfg.qk_norm and "q_norm" in params:
        q = L.rmsnorm(params["q_norm"], q, cfg.norm_eps)
        k = L.rmsnorm(params["k_norm"], k, cfg.norm_eps)
    return q, k, v


def attend_chunked(
    q: jax.Array,  # (B,S,H,Hd)
    k: jax.Array,  # (B,Sk,Kh,Hd)
    v: jax.Array,
    *,
    q_pos: jax.Array,  # (B,S)
    k_pos: jax.Array,  # (B,Sk)
    causal: bool = True,
    window=None,
    attn_softcap: float = 0.0,
    chunk: int = 512,
    scale: Optional[float] = None,
) -> jax.Array:
    """Query-chunked attention: bounds the live (B,H,chunk,Sk) score tensor
    instead of materializing (B,H,S,Sk). The backward pass recomputes the
    chunk body (jax.checkpoint), so it also never holds more than one
    chunk of probabilities — the XLA-level analogue of flash attention,
    used whenever the Pallas kernel is not routed.
    """
    b, s, h, hd = q.shape
    pad = (-s) % chunk
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        q_pos = jnp.pad(q_pos, ((0, 0), (0, pad)), constant_values=-1)
    nq = q.shape[1] // chunk

    @jax.checkpoint
    def body(carry, xs):
        qc, qpc = xs  # (B,chunk,H,Hd), (B,chunk)
        out = attend(qc, k, v, q_pos=qpc, k_pos=k_pos, causal=causal,
                     window=window, attn_softcap=attn_softcap, scale=scale)
        return carry, out

    qs = q.reshape(b, nq, chunk, h, hd).transpose(1, 0, 2, 3, 4)
    ps = q_pos.reshape(b, nq, chunk).transpose(1, 0, 2)
    _, outs = jax.lax.scan(body, None, (qs, ps))
    out = outs.transpose(1, 0, 2, 3, 4).reshape(b, nq * chunk, h, hd)
    return out[:, :s]


# sequences at least this long use attend_chunked on the prefill/train path
CHUNKED_ATTN_THRESHOLD = 2048
CHUNK_Q = 512


def attn_prefill(
    params,
    cfg: ModelConfig,
    x: jax.Array,  # (B,S,D)
    positions: jax.Array,  # (B,S) or (S,)
    *,
    window=None,
    causal: bool = True,
    kv_x: Optional[jax.Array] = None,  # cross-attention source
    kv_positions: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """Full-sequence attention. Returns (out, (k, v)) so callers can seed a
    decode cache from the prefill pass."""
    q, k, v = project_qkv(params, cfg, x, kv_x)
    is_cross = kv_x is not None
    scale = cfg.resolved_attn_scale
    if not is_cross:
        if cfg.position_embedding == "rope":
            q = L.apply_rope(q, positions, cfg.rope_theta)
            k = L.apply_rope(k, positions, cfg.rope_theta)
        kv_pos = positions
    else:
        kv_pos = (kv_positions if kv_positions is not None
                  else jnp.arange(kv_x.shape[1]))
    if cfg.use_pallas and not is_cross and causal:
        from repro.kernels.flash_attention import ops as flash_ops
        out = flash_ops.flash_attention(
            q, k, v,
            causal=True,
            window=int(window) if isinstance(window, int) else None,
            softcap=cfg.attn_softcap,
            scale=scale,
        )
    else:
        q_pos2 = positions if positions.ndim == 2 else positions[None]
        k_pos2 = kv_pos if kv_pos.ndim == 2 else kv_pos[None]
        if q.shape[1] >= CHUNKED_ATTN_THRESHOLD:
            out = attend_chunked(
                q, k, v,
                q_pos=jnp.broadcast_to(q_pos2, q.shape[:2]),
                k_pos=jnp.broadcast_to(k_pos2, k.shape[:2]),
                causal=causal, window=window,
                attn_softcap=cfg.attn_softcap, chunk=CHUNK_Q, scale=scale)
        else:
            out = attend(
                q, k, v,
                q_pos=q_pos2,
                k_pos=k_pos2,
                causal=causal,
                window=window,
                attn_softcap=cfg.attn_softcap,
                scale=scale,
            )
    out = out.reshape(*out.shape[:-2], -1)
    return jnp.einsum("...e,ed->...d", out, params["wo"]), (k, v)


def attn_decode(
    params,
    cfg: ModelConfig,
    x: jax.Array,  # (B,1,D)
    cache_k: jax.Array,  # (B,Smax,K,Hd)
    cache_v: jax.Array,
    cache_len: jax.Array,  # scalar int32 — tokens already in cache
    *,
    window=None,
    ring: bool = False,
) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """One-token decode against a KV cache.

    ``ring=True`` treats the cache as a ring buffer of size Smax (used for
    local sliding-window layers where Smax == window): the new KV overwrites
    slot ``cache_len % Smax`` and masking is done by recovering absolute
    positions of each slot.
    """
    b, _, _ = x.shape
    smax = cache_k.shape[1]
    pos = jnp.full((b, 1), cache_len, dtype=jnp.int32)  # query abs position
    q, k_new, v_new = project_qkv(params, cfg, x)
    if cfg.position_embedding == "rope":
        q = L.apply_rope(q, pos, cfg.rope_theta)
        k_new = L.apply_rope(k_new, pos, cfg.rope_theta)
    scale = cfg.resolved_attn_scale

    slot = jnp.where(ring, cache_len % smax, jnp.minimum(cache_len, smax - 1))
    cache_k = jax.lax.dynamic_update_slice_in_dim(cache_k, k_new.astype(cache_k.dtype), slot, axis=1)
    cache_v = jax.lax.dynamic_update_slice_in_dim(cache_v, v_new.astype(cache_v.dtype), slot, axis=1)

    idx = jnp.arange(smax, dtype=jnp.int32)
    if ring:
        # absolute position of each slot after the write
        wraps = (cache_len // smax) * smax
        k_pos = jnp.where(idx <= (cache_len % smax), wraps + idx, wraps - smax + idx)
        valid = k_pos >= 0
    else:
        k_pos = idx
        valid = idx <= cache_len
    if cfg.use_pallas and not ring and window is None:
        # TPU fast path: flash-decode kernel (one cache pass, VMEM-resident
        # online softmax; interpret-mode on CPU)
        from repro.kernels.flash_decode import ops as fd_ops
        out = fd_ops.flash_decode(
            q, cache_k, cache_v, cache_len + 1,
            softcap=cfg.attn_softcap, scale=scale,
        ).reshape(b, 1, cfg.num_heads, -1)
        out = out.reshape(b, 1, -1)
        return (jnp.einsum("...e,ed->...d", out, params["wo"]),
                (cache_k, cache_v))
    out = attend(
        q, cache_k, cache_v,
        q_pos=pos,
        k_pos=k_pos[None].astype(jnp.int32),
        causal=True,
        window=window,
        attn_softcap=cfg.attn_softcap,
        kv_valid=jnp.broadcast_to(valid[None], (b, smax)),
        scale=scale,
    )
    out = out.reshape(b, 1, -1)
    return jnp.einsum("...e,ed->...d", out, params["wo"]), (cache_k, cache_v)


def attn_cross_decode(
    params,
    cfg: ModelConfig,
    x: jax.Array,  # (B,1,D)
    cross_k: jax.Array,  # (B,Tenc,K,Hd) — precomputed from encoder output
    cross_v: jax.Array,
) -> jax.Array:
    hd = cfg.resolved_head_dim
    q = jnp.einsum("...d,de->...e", x, params["wq"])
    q = q.reshape(*q.shape[:-1], cfg.num_heads, hd)
    tenc = cross_k.shape[1]
    out = attend(
        q, cross_k, cross_v,
        q_pos=jnp.zeros((x.shape[0], 1), jnp.int32),
        k_pos=jnp.zeros((1, tenc), jnp.int32),
        causal=False,
        window=None,
        attn_softcap=cfg.attn_softcap,
        scale=cfg.resolved_attn_scale,
    )
    out = out.reshape(x.shape[0], 1, -1)
    return jnp.einsum("...e,ed->...d", out, params["wo"])


def precompute_cross_kv(params, cfg: ModelConfig, enc_out: jax.Array):
    """Project encoder outputs into decoder cross-attention K/V once."""
    hd = cfg.resolved_head_dim
    k = jnp.einsum("...d,de->...e", enc_out, params["wk"])
    v = jnp.einsum("...d,de->...e", enc_out, params["wv"])
    k = k.reshape(*k.shape[:-1], cfg.num_kv_heads, hd)
    v = v.reshape(*v.shape[:-1], cfg.num_kv_heads, hd)
    return k, v
