"""Pallas TPU kernel for the Mamba2 SSD chunked scan.

TPU-native adaptation of the SSD algorithm (arXiv:2405.21060):
- the chunk axis is the innermost *sequential* grid dimension; the running
  (P, N) inter-chunk state lives in VMEM scratch across grid steps (the GPU
  version uses a separate state-passing kernel + global memory round-trip);
- within a chunk, the quadratic "attention" term and the state update are
  MXU matmuls over (Q, N) x (N, Q) and (P, Q) x (Q, N) tiles; Q (chunk) and
  N (state) are sized to 128-multiples by the wrapper;
- per-head scalars A, D index via BlockSpecs (SMEM scalar prefetch on real
  hardware; plain VMEM blocks suffice for interpret-mode validation).

Grid: (batch, heads, chunks). B/C tensors are shared across the heads of a
group — their BlockSpec index map folds h -> h // heads_per_group, so group
tiles are fetched once per group, not per head.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(
    x_ref,      # (1,1,Q,P)
    dt_ref,     # (1,1,Q)
    a_ref,      # (1,)
    b_ref,      # (1,1,Q,N)
    c_ref,      # (1,1,Q,N)
    d_ref,      # (1,)
    h0_ref,     # (1,1,P,N)
    y_ref,      # out: (1,1,Q,P)
    hf_ref,     # out: (1,1,P,N)
    state_ref,  # scratch: (P,N) f32
    *,
    chunk: int,
):
    ic = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(ic == 0)
    def _init():
        state_ref[...] = h0_ref[0, 0].astype(jnp.float32)

    x = x_ref[0, 0].astype(jnp.float32)      # (Q, P)
    dt = dt_ref[0, 0].astype(jnp.float32)    # (Q,)
    bm = b_ref[0, 0].astype(jnp.float32)     # (Q, N)
    cm = c_ref[0, 0].astype(jnp.float32)     # (Q, N)
    a_h = a_ref[0].astype(jnp.float32)       # scalar
    d_h = d_ref[0].astype(jnp.float32)

    a = dt * a_h                              # (Q,) log decay
    a_cum = jnp.cumsum(a)

    # intra-chunk quadratic term
    seg = a_cum[:, None] - a_cum[None, :]     # (Q, Q)
    causal = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    lmat = jnp.where(causal, jnp.exp(seg), 0.0)
    scores = jax.lax.dot_general(cm, bm, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    att = scores * lmat * dt[None, :]
    y = jax.lax.dot_general(att, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)

    # inter-chunk contribution from the carried state
    state = state_ref[...]                    # (P, N)
    y_inter = jax.lax.dot_general(cm, state, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    y = y + y_inter * jnp.exp(a_cum)[:, None]

    # state update: h <- h * exp(sum a) + sum_j decay_j dt_j x_j B_j^T
    decay_end = jnp.exp(a_cum[-1] - a_cum)    # (Q,)
    xw = x * (dt * decay_end)[:, None]        # (Q, P)
    state_new = state * jnp.exp(a_cum[-1]) + jax.lax.dot_general(
        xw, bm, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    state_ref[...] = state_new

    y_ref[0, 0] = (y + x * d_h).astype(y_ref.dtype)

    @pl.when(ic == nc - 1)
    def _final():
        hf_ref[0, 0] = state_new.astype(hf_ref.dtype)


def ssd_scan(
    x: jax.Array,    # (B, H, S, P) fp32
    dt: jax.Array,   # (B, H, S)
    A: jax.Array,    # (H,)
    Bm: jax.Array,   # (B, G, S, N)
    Cm: jax.Array,   # (B, G, S, N)
    D: jax.Array,    # (H,)
    h0: jax.Array,   # (B, H, P, N)
    *,
    chunk: int,
    interpret: bool,
):
    b, h, s, p = x.shape
    g, n = Bm.shape[1], Bm.shape[3]
    hpg = h // g
    nc = s // chunk

    kernel = functools.partial(_ssd_kernel, chunk=chunk)
    return pl.pallas_call(
        kernel,
        grid=(b, h, nc),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, p), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, chunk), lambda b, h, c: (b, h, c)),
            pl.BlockSpec((1,), lambda b, h, c: (h,)),
            pl.BlockSpec((1, 1, chunk, n), lambda b, h, c: (b, h // hpg, c, 0)),
            pl.BlockSpec((1, 1, chunk, n), lambda b, h, c: (b, h // hpg, c, 0)),
            pl.BlockSpec((1,), lambda b, h, c: (h,)),
            pl.BlockSpec((1, 1, p, n), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, p), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, p, n), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, s, p), x.dtype),
            jax.ShapeDtypeStruct((b, h, p, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        interpret=interpret,
    )(x, dt, A, Bm, Cm, D, h0)
