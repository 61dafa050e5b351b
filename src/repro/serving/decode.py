"""Serving step functions and host-side generation loops.

``make_serve_step`` produces the decode step that the batcher and
``generate`` jit: one token in, (sampled token, updated cache) out.
Sampling is greedy by default; temperature sampling threads a PRNG key.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import api
from repro.models.config import ModelConfig

#: decode-step donation declaration: argument 2 is the KV cache, which is
#: consumed and replaced every step — donating it lets XLA update in
#: place instead of holding two generations of the cache at peak. The
#: compile-path donation lint (``repro.analysis.compiled``) verifies the
#: compiled module actually aliases it.
SERVE_STEP_DONATE = (2,)


def make_serve_step(cfg: ModelConfig, temperature: float = 0.0):
    def serve_step(params, token, cache, key=None):
        logits, cache = api.decode_step(params, cfg, token, cache)
        logits = logits[:, -1, :]
        if temperature > 0.0 and key is not None:
            next_tok = jax.random.categorical(key, logits / temperature, axis=-1)
        else:
            next_tok = jnp.argmax(logits, axis=-1)
        return next_tok[:, None].astype(jnp.int32), cache

    return serve_step


@functools.lru_cache(maxsize=64)
def serve_step_jit(cfg: ModelConfig, temperature: float = 0.0):
    """Memoized jitted decode step with cache donation.

    ``jax.jit`` keys its trace cache on function identity, so wrapping a
    fresh ``make_serve_step(cfg)`` closure per call retraces every time.
    ``ModelConfig`` is frozen/hashable, so one jitted step per
    ``(cfg, temperature)`` serves every ``generate()`` call — the
    compile-path recompile lint checks this identity holds."""
    return jax.jit(make_serve_step(cfg, temperature),
                   donate_argnums=SERVE_STEP_DONATE)


def make_prefill(cfg: ModelConfig, max_len: int):
    def prefill_fn(params, **inputs):
        return api.prefill(params, cfg, max_len, **inputs)
    return prefill_fn


def generate(
    params,
    cfg: ModelConfig,
    prompt: jax.Array,  # (B, S) int32
    steps: int,
    *,
    max_len: Optional[int] = None,
    temperature: float = 0.0,
    seed: int = 0,
    extra_inputs: Optional[Dict[str, Any]] = None,
) -> np.ndarray:
    """Host-side autoregressive generation (examples / JaxBackend)."""
    b, s = prompt.shape
    max_len = max_len or (s + steps + 8)
    inputs = dict(extra_inputs or {})
    inputs["tokens"] = prompt
    logits, cache = api.prefill(params, cfg, max_len, **inputs)
    serve_step = serve_step_jit(cfg, temperature)
    if temperature > 0.0:
        tok = jax.random.categorical(
            jax.random.PRNGKey(seed), logits[:, -1, :] / temperature, axis=-1
        )[:, None].astype(jnp.int32)
    else:
        tok = jnp.argmax(logits[:, -1, :], axis=-1)[:, None].astype(jnp.int32)
    out = [np.asarray(tok)]
    key = jax.random.PRNGKey(seed + 1)
    for _ in range(steps - 1):
        key, sub = jax.random.split(key)
        tok, cache = serve_step(params, tok, cache,
                                sub if temperature > 0 else None)
        out.append(np.asarray(tok))
    return np.concatenate(out, axis=1)
