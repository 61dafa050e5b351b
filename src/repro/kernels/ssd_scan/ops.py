"""Jit'd wrapper: model layout (B,S,H,P) -> kernel layout (B,H,S,P).

This is the routing target of ``ssm.mamba_prefill`` when cfg.use_pallas.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import resolve_interpret
from repro.kernels.ssd_scan.kernel import ssd_scan


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd(
    x: jax.Array,    # (B, S, H, P) fp32 — model layout
    dt: jax.Array,   # (B, S, H)
    A: jax.Array,    # (H,)
    Bm: jax.Array,   # (B, S, G, N)
    Cm: jax.Array,   # (B, S, G, N)
    D: jax.Array,    # (H,)
    chunk: int,
    initial_state: Optional[jax.Array] = None,  # (B, H, P, N)
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array]:
    b, s, h, p = x.shape
    n = Bm.shape[-1]
    g = Bm.shape[2]
    if chunk <= 0:
        raise ValueError(f"ssd_scan: chunk must be positive, got {chunk}")
    if s % chunk != 0:
        raise ValueError(
            f"ssd_scan: seq axis not divisible — seq={s} is not a "
            f"multiple of chunk={chunk}; pad the sequence first (the "
            f"kernel would silently truncate the tail chunk)")
    if g <= 0 or h % g != 0:
        raise ValueError(
            f"ssd_scan: heads axis invalid — x has {h} heads, B/C have "
            f"{g} groups; needs heads % groups == 0")
    h0 = (initial_state if initial_state is not None
          else jnp.zeros((b, h, p, n), jnp.float32))
    y, hf = ssd_scan(
        x.transpose(0, 2, 1, 3),
        dt.transpose(0, 2, 1),
        A,
        Bm.transpose(0, 2, 1, 3),
        Cm.transpose(0, 2, 1, 3),
        D,
        h0.astype(jnp.float32),
        chunk=chunk,
        interpret=resolve_interpret(interpret),
    )
    return y.transpose(0, 2, 1, 3), hf
