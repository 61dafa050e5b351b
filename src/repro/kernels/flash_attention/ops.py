"""Jit'd public wrapper around the flash-attention Pallas kernel.

Handles layout (B,S,H,Hd) <-> kernel layout (B,Kh,G,S,Hd), sequence padding
to block multiples, and head_dim padding to a 128 multiple (MXU lane width).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import resolve_interpret
from repro.kernels.flash_attention.kernel import (
    DEFAULT_BLOCK_K, DEFAULT_BLOCK_Q, flash_attention_gqa)


def _pad_to(x: jax.Array, axis: int, mult: int) -> jax.Array:
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "softcap", "block_q", "block_k",
                     "interpret", "scale"))
def flash_attention(
    q: jax.Array,  # (B, S, H, Hd)
    k: jax.Array,  # (B, S, K, Hd)
    v: jax.Array,  # (B, S, K, Hd)
    *,
    causal: bool = True,
    window: Optional[int] = None,
    softcap: float = 0.0,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: Optional[bool] = None,
    scale: Optional[float] = None,  # None -> head_dim ** -0.5
) -> jax.Array:
    b, s, h, hd = q.shape
    kh = k.shape[2]
    if kh <= 0 or h % kh != 0:
        raise ValueError(
            f"flash_attention: heads axis invalid — q has {h} heads, k/v "
            f"have {kh} kv-heads; GQA needs heads % kv_heads == 0")
    if block_q <= 0 or block_k <= 0:
        raise ValueError(
            f"flash_attention: block shape must be positive, got "
            f"block_q={block_q}, block_k={block_k}")
    g = h // kh

    # kernel layout: (B, Kh, G, S, Hd) for q; (B, Kh, S, Hd) for k/v
    qk = q.reshape(b, s, kh, g, hd).transpose(0, 2, 3, 1, 4)
    kk = k.transpose(0, 2, 1, 3)
    vk = v.transpose(0, 2, 1, 3)

    # pad head_dim to MXU lane multiple and seq to block multiple
    hd_pad = max(128, ((hd + 127) // 128) * 128)
    if hd_pad != hd:
        qk = _pad_to(qk, 4, hd_pad)
        kk = _pad_to(kk, 3, hd_pad)
        vk = _pad_to(vk, 3, hd_pad)
    bq = min(block_q, max(s, 8))
    bk = min(block_k, max(s, 8))
    s_pad = max(((s + bq - 1) // bq) * bq, ((s + bk - 1) // bk) * bk)
    if s_pad != s:
        qk = _pad_to(qk, 3, s_pad)
        kk = _pad_to(kk, 2, s_pad)
        vk = _pad_to(vk, 2, s_pad)

    # scale uses the TRUE head_dim, not the padded one
    out = flash_attention_gqa(
        qk, kk, vk,
        causal=causal,
        window=int(window or 0),
        softcap=softcap,
        block_q=bq,
        block_k=bk,
        interpret=resolve_interpret(interpret),
        scale=hd ** -0.5 if scale is None else scale,
    )
    out = out[:, :, :, :s, :hd]
    return out.transpose(0, 3, 1, 2, 4).reshape(b, s, h, hd)
