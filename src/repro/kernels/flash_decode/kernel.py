"""Pallas TPU flash-decode kernel: one query position vs a long KV cache.

This is the serving hot spot: every decode step streams the KV cache. The
kernel:

- never expands GQA: the grid iterates (batch, kv-head, kv-blocks) and the
  per-kv-head query group (G = H/K rows) rides in VMEM as a (G, Hd) tile;
- reads the cache in a (B, K, S, Hd) layout, so a K/V block is a
  (block_s, Hd) tile whose last two dims meet the TPU's (8, 128) tiling
  rule;
- runs online softmax over kv blocks (innermost sequential grid dim) with
  (G,1)/(G,Hd) running max/denominator/accumulator in VMEM scratch: the
  kernel itself reads its K/V operands once, and no (H, S) score tensor
  reaches HBM;
- masks by the *dynamic* cache length: ``valid_len`` is a (1,) int32
  scalar-prefetch operand, so it sits in SMEM before the grid starts and
  blocks wholly past it skip their compute.

Supports GQA/MQA, softcap. Ring-buffer local caches use the jnp path (the
ring index arithmetic is cheap at window size).

The model keeps its cache as (B, S, K, Hd), so the wrapper (``ops.py``)
transposes all of K and V to (B, K, S, Hd) on every decode step, and
pads Hd up to a multiple of 128 (64 -> 128 for llama): a full, padded
copy of the cache is written to HBM and read back before the kernel
starts. Counting HBM traffic, one step costs several cache-sized passes,
not one. Until the cache owner stores (B, K, S, Hd) itself, a comparison
of this path against the jnp one must count that copy.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)

DEFAULT_BLOCK_S = 512


def _decode_kernel(
    len_ref,   # SMEM (1,) int32: number of valid cache entries
    q_ref,     # (1, 1, G, Hd)
    k_ref,     # (1, 1, bs, Hd)
    v_ref,     # (1, 1, bs, Hd)
    o_ref,     # (1, 1, G, Hd)
    m_ref, l_ref, acc_ref,  # scratch: (G,1), (G,1), (G,Hd) fp32
    *,
    scale: float,
    softcap: float,
    block_s: int,
):
    isb = pl.program_id(2)
    nsb = pl.num_programs(2)

    @pl.when(isb == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    valid_len = len_ref[0]
    s_start = isb * block_s

    @pl.when(s_start < valid_len)  # skip fully-invalid cache blocks
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)   # (G, Hd)
        k = k_ref[0, 0].astype(jnp.float32)   # (bs, Hd)
        v = v_ref[0, 0].astype(jnp.float32)

        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if softcap > 0.0:
            s = softcap * jnp.tanh(s / softcap)
        pos = s_start + jax.lax.broadcasted_iota(jnp.int32,
                                                 (q.shape[0], k.shape[0]), 1)
        s = jnp.where(pos < valid_len, s, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(isb == nsb - 1)
    def _done():
        denom = l_ref[...]
        denom = jnp.where(denom == 0.0, 1.0, denom)
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def flash_decode_gqa(
    q: jax.Array,          # (B, K, G, Hd)
    k: jax.Array,          # (B, K, S, Hd)
    v: jax.Array,
    valid_len: jax.Array,  # (1,) int32
    *,
    interpret: bool,
    softcap: float = 0.0,
    block_s: int = DEFAULT_BLOCK_S,
    scale: float = 0.0,
) -> jax.Array:
    b, kh, g, hd = q.shape
    s = k.shape[2]
    block_s = min(block_s, s)
    nsb = pl.cdiv(s, block_s)
    scale = scale or hd ** -0.5
    kernel = functools.partial(_decode_kernel, scale=scale, softcap=softcap,
                               block_s=block_s)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, kh, nsb),
        in_specs=[
            pl.BlockSpec((1, 1, g, hd), lambda b, h, i, n: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, block_s, hd), lambda b, h, i, n: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_s, hd), lambda b, h, i, n: (b, h, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, hd),
                               lambda b, h, i, n: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, hd), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kh, g, hd), q.dtype),
        interpret=interpret,
    )(valid_len, q, k, v)
