"""Jit'd wrapper: model layout (B,1,H,Hd) query + (B,S,K,Hd) cache."""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import resolve_interpret
from repro.kernels.flash_decode.kernel import DEFAULT_BLOCK_S, flash_decode_gqa


@functools.partial(jax.jit,
                   static_argnames=("softcap", "block_s", "interpret",
                                    "scale"))
def flash_decode(
    q: jax.Array,          # (B, 1, H, Hd)
    k: jax.Array,          # (B, S, K, Hd)
    v: jax.Array,
    valid_len: jax.Array,  # scalar int32
    *,
    softcap: float = 0.0,
    block_s: int = DEFAULT_BLOCK_S,
    interpret: Optional[bool] = None,
    scale: Optional[float] = None,  # None -> head_dim ** -0.5
) -> jax.Array:
    b, _, h, hd = q.shape
    kh = k.shape[2]
    if kh <= 0 or h % kh != 0:
        raise ValueError(
            f"flash_decode: heads axis invalid — q has {h} heads, k/v "
            f"cache has {kh} kv-heads; GQA needs heads % kv_heads == 0")
    if block_s <= 0:
        raise ValueError(
            f"flash_decode: block shape must be positive, got "
            f"block_s={block_s}")
    g = h // kh
    qg = q.reshape(b, kh, g, hd)
    # kernel layout (B, K, S, Hd): a K/V block's last two dims are then
    # (block_s, hd_pad), which the TPU's (8, 128) tiling rule accepts.
    # This copies the whole cache every step (see kernel.py's docstring)
    k = k.transpose(0, 2, 1, 3)
    v = v.transpose(0, 2, 1, 3)
    # pad head_dim to the MXU lane multiple
    hd_pad = max(128, ((hd + 127) // 128) * 128)
    if hd_pad != hd:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, 0), (0, hd_pad - hd)))
        k = jnp.pad(k, ((0, 0), (0, 0), (0, 0), (0, hd_pad - hd)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, hd_pad - hd)))
    s = k.shape[2]
    bs = min(block_s, max(s, 8))
    pad_s = (-s) % bs
    if pad_s:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_s), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_s), (0, 0)))
    out = flash_decode_gqa(
        qg, k, v, jnp.asarray(valid_len, jnp.int32).reshape(1),
        softcap=softcap, block_s=bs, interpret=resolve_interpret(interpret),
        scale=hd ** -0.5 if scale is None else scale)
    return out[..., :hd].reshape(b, 1, h, hd)
