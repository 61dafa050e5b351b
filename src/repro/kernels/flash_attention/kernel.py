"""Pallas TPU flash-attention kernel (blockwise online softmax).

TPU-native design notes (vs. a CUDA port):
- tiling is MXU-aligned: q/k blocks are (block_q, head_dim) x (block_k,
  head_dim) with head_dim padded to a multiple of 128 by the wrapper;
- the kv loop is the innermost *sequential* grid dimension — on TPU, grid
  steps that revisit the same output block execute in order on one core, so
  the online-softmax running state (m, l, acc) lives in VMEM scratch across
  grid steps instead of registers;
- GQA is expressed through BlockSpec index maps: the kv BlockSpec ignores
  the q-head-group grid coordinate, so kv tiles are fetched once per kv head
  (never materialized H/K times in HBM);
- causal and sliding-window masking prune whole kv blocks via ``pl.when``
  (the MXU never sees fully-masked tiles).

Supports: causal masking, sliding-window (gemma local layers), attention
logit softcap (gemma2/grok-1), GQA/MQA.
"""

from __future__ import annotations

import functools
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)

DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 512


def _flash_kernel(
    q_ref, k_ref, v_ref,  # (1,1,1,bq,hd), (1,1,bk,hd), (1,1,bk,hd)
    o_ref,                # (1,1,1,bq,hd)
    m_ref, l_ref, acc_ref,  # scratch: (bq,1), (bq,1), (bq,hd) fp32
    *,
    scale: float,
    causal: bool,
    window: int,          # 0 = unlimited
    softcap: float,
    block_q: int,
    block_k: int,
    seq_len: int,
):
    iq = pl.program_id(3)
    ik = pl.program_id(4)
    nk = pl.num_programs(4)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = iq * block_q
    k_start = ik * block_k

    # block-level pruning: skip kv blocks that are entirely masked
    relevant = True
    if causal:
        relevant = k_start <= q_start + block_q - 1  # some k <= some q
    if window > 0:
        # newest q position minus oldest k position must be < window somewhere:
        # skip when (q_start - (k_start+block_k-1)) >= window
        relevant = jnp.logical_and(
            relevant, q_start - (k_start + block_k - 1) < window)

    @pl.when(relevant)
    def _body():
        q = q_ref[0, 0, 0].astype(jnp.float32)   # (bq, hd)
        k = k_ref[0, 0].astype(jnp.float32)      # (bk, hd)
        v = v_ref[0, 0].astype(jnp.float32)      # (bk, hd)

        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if softcap > 0.0:
            s = softcap * jnp.tanh(s / softcap)

        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        delta = q_pos - k_pos
        mask = k_pos < seq_len  # padding
        if causal:
            mask = jnp.logical_and(mask, delta >= 0)
        if window > 0:
            mask = jnp.logical_and(mask, delta < window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                       # (bq, 1)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                    # (bq, bk)
        alpha = jnp.exp(m_prev - m_new)           # (bq, 1)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        denom = l_ref[...]
        denom = jnp.where(denom == 0.0, 1.0, denom)
        o_ref[0, 0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def flash_attention_gqa(
    q: jax.Array,  # (B, Kh, G, S, Hd) — q heads grouped by kv head
    k: jax.Array,  # (B, Kh, S, Hd)
    v: jax.Array,  # (B, Kh, S, Hd)
    *,
    causal: bool = True,
    window: int = 0,
    softcap: float = 0.0,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool,
    scale: float = 0.0,  # 0 -> head_dim**-0.5 (pass explicitly when padded)
) -> jax.Array:
    b, kh, g, s, hd = q.shape
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    nq = pl.cdiv(s, block_q)
    nk = pl.cdiv(s, block_k)
    scale = scale or hd ** -0.5

    kernel = functools.partial(
        _flash_kernel,
        scale=scale, causal=causal, window=window, softcap=softcap,
        block_q=block_q, block_k=block_k, seq_len=s,
    )
    return pl.pallas_call(
        kernel,
        grid=(b, kh, g, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, 1, block_q, hd),
                         lambda b, h, g, iq, ik: (b, h, g, iq, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda b, h, g, iq, ik: (b, h, ik, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda b, h, g, iq, ik: (b, h, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, block_q, hd),
                               lambda b, h, g, iq, ik: (b, h, g, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b, kh, g, s, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
