"""Shared neural-net building blocks (pure functions over param pytrees).

Everything here is functional: ``init_*`` builds parameter pytrees from a PRNG
key; ``apply``-style functions are pure and jit/scan friendly. Parameters are
plain nested dicts so they serialize, shard, and stack (for scan-over-layers)
without any module framework.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.config import ModelConfig

# --------------------------------------------------------------------------
# dtype helpers
# --------------------------------------------------------------------------

_DTYPES = {
    "bfloat16": jnp.bfloat16,
    "float32": jnp.float32,
    "float16": jnp.float16,
}


def dtype_of(name: str):
    return _DTYPES[name]


# --------------------------------------------------------------------------
# initializers
# --------------------------------------------------------------------------


def trunc_normal(key, shape, std: float, dtype) -> jax.Array:
    """A normal truncated at two deviations, scaled by ``std``."""
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
            * std).astype(dtype)


def dense_init(key, in_dim: int, out_dim: int, dtype) -> jax.Array:
    """Truncated-normal fan-in init (matches common LM practice)."""
    return trunc_normal(key, (in_dim, out_dim), 1.0 / np.sqrt(in_dim), dtype)


def embed_init(key, vocab: int, dim: int, dtype, std: float = 0.02) -> jax.Array:
    return (jax.random.normal(key, (vocab, dim), jnp.float32) * std).astype(dtype)


def zeros_init(shape, dtype) -> jax.Array:
    return jnp.zeros(shape, dtype)


def ones_init(shape, dtype) -> jax.Array:
    return jnp.ones(shape, dtype)


# --------------------------------------------------------------------------
# RMSNorm
# --------------------------------------------------------------------------


def init_rmsnorm(dim: int, dtype=jnp.float32):
    # Norm scales are kept fp32: tiny memory, avoids bf16 rounding of the gain.
    return {"scale": jnp.zeros((dim,), jnp.float32)}


def rmsnorm(params, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    """(1+scale) RMS norm (gemma/llama style), computed in fp32."""
    orig_dtype = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    normed = xf * jax.lax.rsqrt(var + eps)
    return (normed * (1.0 + params["scale"])).astype(orig_dtype)


# --------------------------------------------------------------------------
# Rotary position embeddings
# --------------------------------------------------------------------------


def rope_frequencies(head_dim: int, theta: float) -> jax.Array:
    """Inverse frequencies, shape (head_dim // 2,), fp32."""
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotate pairs (x[..., :half], x[..., half:]) by position-dependent angles.

    x: (..., S, H, Hd) or (..., S, Hd); positions: broadcastable to (..., S).
    """
    half = x.shape[-1] // 2
    freqs = rope_frequencies(x.shape[-1], theta)  # (half,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, half)
    if x.ndim == angles.ndim + 1:  # head axis present
        angles = angles[..., None, :]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------
# SwiGLU MLP
# --------------------------------------------------------------------------


def init_mlp(key, d_model: int, d_ff: int, dtype):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w_gate": dense_init(k1, d_model, d_ff, dtype),
        "w_up": dense_init(k2, d_model, d_ff, dtype),
        "w_down": dense_init(k3, d_ff, d_model, dtype),
    }


def mlp(params, x: jax.Array) -> jax.Array:
    gate = jnp.einsum("...d,df->...f", x, params["w_gate"])
    up = jnp.einsum("...d,df->...f", x, params["w_up"])
    return jnp.einsum("...f,fd->...d", jax.nn.silu(gate) * up, params["w_down"])


def init_gelu_mlp(key, d_model: int, d_ff: int, dtype):
    """Classic 2-matrix GELU MLP (whisper)."""
    k1, k2 = jax.random.split(key)
    return {
        "w_in": dense_init(k1, d_model, d_ff, dtype),
        "w_out": dense_init(k2, d_ff, d_model, dtype),
    }


def gelu_mlp(params, x: jax.Array) -> jax.Array:
    h = jnp.einsum("...d,df->...f", x, params["w_in"])
    return jnp.einsum("...f,fd->...d", jax.nn.gelu(h), params["w_out"])


# --------------------------------------------------------------------------
# Embedding / unembedding
# --------------------------------------------------------------------------


def init_embedding(key, cfg: ModelConfig):
    dtype = dtype_of(cfg.param_dtype)
    # drawn so that the embedding, once multiplied, has std 0.02: at
    # granite's x12, a table of std 0.02 outweighs every layer's branch and
    # the tied head then ranks the input token first at every position
    params = {"tokens": embed_init(key, cfg.vocab_size, cfg.d_model, dtype,
                                   std=0.02 / cfg.embedding_multiplier)}
    if not cfg.tie_embeddings:
        k2 = jax.random.fold_in(key, 1)
        params["unembed"] = embed_init(k2, cfg.vocab_size, cfg.d_model, dtype)
    return params


def embed(params, cfg: ModelConfig, tokens: jax.Array) -> jax.Array:
    x = params["tokens"][tokens]
    if cfg.family in ("dense", "vlm"):  # gemma-style sqrt(d) scaling is harmless
        pass
    return x.astype(dtype_of(cfg.dtype))


def unembed(params, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    table = params.get("unembed", params["tokens"])
    logits = jnp.einsum("...d,vd->...v", x, table).astype(jnp.float32)
    if cfg.logits_scaling != 1.0:
        logits = logits / cfg.logits_scaling
    if cfg.final_softcap > 0.0:
        c = cfg.final_softcap
        logits = c * jnp.tanh(logits / c)
    return logits


# --------------------------------------------------------------------------
# misc
# --------------------------------------------------------------------------


def softcap(scores: jax.Array, cap: float) -> jax.Array:
    if cap and cap > 0.0:
        return cap * jnp.tanh(scores / cap)
    return scores


def count_params(params) -> int:
    return int(sum(np.prod(p.shape) for p in jax.tree_util.tree_leaves(params)))
