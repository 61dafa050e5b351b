"""Jit'd wrapper for the fused expert-FFN kernel (pads C/F to tiles)."""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import resolve_interpret
from repro.kernels.moe_ffn.kernel import moe_expert_ffn


@functools.partial(jax.jit, static_argnames=("block_c", "block_f", "interpret"))
def expert_ffn(
    x: jax.Array,       # (G, E, C, D)
    w_gate: jax.Array,  # (E, D, F)
    w_up: jax.Array,
    w_down: jax.Array,  # (E, F, D)
    *,
    block_c: int = 128,
    block_f: int = 512,
    interpret: Optional[bool] = None,
) -> jax.Array:
    g, e, c, d = x.shape
    f = w_gate.shape[-1]
    if block_c <= 0 or block_f <= 0:
        raise ValueError(
            f"moe_ffn: block shape must be positive, got "
            f"block_c={block_c}, block_f={block_f}")
    if w_gate.shape[0] != e or w_gate.shape[1] != d:
        raise ValueError(
            f"moe_ffn: experts axis mismatch — x is (G,E,C,D)="
            f"{x.shape} but w_gate is (E,D,F)={w_gate.shape}")
    bc = min(block_c, max(c, 8))
    bf = min(block_f, max(f, 128))
    c_pad = (-c) % bc
    f_pad = (-f) % bf
    if c_pad:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, c_pad), (0, 0)))
    if f_pad:
        w_gate = jnp.pad(w_gate, ((0, 0), (0, 0), (0, f_pad)))
        w_up = jnp.pad(w_up, ((0, 0), (0, 0), (0, f_pad)))
        w_down = jnp.pad(w_down, ((0, 0), (0, f_pad), (0, 0)))
    out = moe_expert_ffn(x, w_gate, w_up, w_down,
                         block_c=bc, block_f=bf, interpret=resolve_interpret(interpret))
    return out[:, :, :c, :]
