"""Chunked cross-entropy loss.

Never materializes the full (B, S, V) logits tensor: the sequence is scanned
in chunks, and each chunk computes logits -> logsumexp -> label logit in
fp32 before being reduced. With gemma3's 262k vocab and 4k sequences this
is the difference between ~70 GB of logits per device and ~0.5 GB.

Under pjit the per-chunk logits einsum contracts d_model and leaves a
(B, chunk, V) intermediate whose vocab axis inherits the embedding table's
"model"-axis sharding, so the logsumexp induces a small all-reduce per chunk
instead of an all-gather of the full logits.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig


def chunked_softmax_xent(
    hidden: jax.Array,      # (B, S, D)
    table: jax.Array,       # (V, D) unembedding table
    labels: jax.Array,      # (B, S) int32
    cfg: ModelConfig,
    *,
    chunk: int = 512,
    mask: Optional[jax.Array] = None,  # (B, S) bool, True = count
) -> Tuple[jax.Array, jax.Array]:
    """Returns (mean nll, token count)."""
    b, s, d = hidden.shape
    chunk = min(chunk, s)
    pad = (-s) % chunk
    if pad:
        hidden = jnp.pad(hidden, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)))
        mask = jnp.pad(mask, ((0, 0), (0, pad))) if mask is not None \
            else jnp.pad(jnp.ones((b, s), bool), ((0, 0), (0, pad)))
    elif mask is None:
        mask = jnp.ones((b, s), bool)
    nc = hidden.shape[1] // chunk

    @jax.checkpoint
    def body(carry, xs):
        # checkpointed: the backward pass recomputes each chunk's logits
        # instead of saving (B, chunk, V) fp32 per chunk across the scan
        nll_sum, count = carry
        h, y, m = xs  # (B, chunk, D), (B, chunk), (B, chunk)
        logits = jnp.einsum("bsd,vd->bsv", h.astype(jnp.float32),
                            table.astype(jnp.float32))
        if cfg.final_softcap > 0.0:
            c = cfg.final_softcap
            logits = c * jnp.tanh(logits / c)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, y[..., None], axis=-1)[..., 0]
        nll = (lse - gold) * m
        return (nll_sum + jnp.sum(nll), count + jnp.sum(m)), None

    xs = (hidden.reshape(b, nc, chunk, d).transpose(1, 0, 2, 3),
          labels.reshape(b, nc, chunk).transpose(1, 0, 2),
          mask.reshape(b, nc, chunk).transpose(1, 0, 2).astype(jnp.float32))
    (nll_sum, count), _ = jax.lax.scan(body, (jnp.zeros(()), jnp.zeros(())), xs)
    return nll_sum / jnp.maximum(count, 1.0), count


def lm_loss(params, cfg: ModelConfig, batch, *, aux_weight: float = 0.01):
    """Full LM loss: forward(hidden) + chunked xent + MoE aux."""
    from repro.models import api  # local import to avoid cycles

    inputs = {k: batch[k] for k in api.input_names(cfg) if k in batch}
    hidden, aux = api.forward(params, cfg, **inputs, return_hidden=True)
    if cfg.family == "vlm" and cfg.num_patches:
        hidden = hidden[:, cfg.num_patches:, :]
    table = params["embed"].get("unembed", params["embed"]["tokens"])
    nll, count = chunked_softmax_xent(hidden, table, batch["labels"], cfg)
    loss = nll + aux_weight * aux
    return loss, {"nll": nll, "aux": aux, "tokens": count}
