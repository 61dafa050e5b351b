"""KV-cache sizing and accounting helpers.

The actual cache pytrees are built by models/{transformer,encdec}.init_cache;
this module centralizes capacity math and byte estimates the scheduler and
cost model consume.
"""

from __future__ import annotations

import jax

from repro.models import api
from repro.models.config import ModelConfig
from repro.models.transformer import layout


def cache_bytes(cfg: ModelConfig, batch: int, max_len: int) -> int:
    """Estimated decode-cache bytes (accounts for ring-buffer local layers)."""
    hd = cfg.resolved_head_dim
    bpe = 2 if cfg.dtype == "bfloat16" else 4
    total = 0
    if cfg.is_encoder_decoder:
        per_layer = 2 * batch * max_len * cfg.num_kv_heads * hd * bpe
        cross = 2 * batch * cfg.encoder_seq_len * cfg.num_kv_heads * hd * bpe
        return cfg.num_layers * (per_layer + cross)
    pattern, n_full, tail = layout(cfg)
    kinds = pattern * n_full + tail
    for kind in kinds:
        if kind == "mamba":
            total += batch * cfg.ssm_nheads * cfg.ssm_head_dim * cfg.ssm_state * 4
            total += batch * (cfg.ssm_conv_width - 1) * cfg.ssm_conv_dim * bpe
        else:
            size = min(cfg.local_window, max_len) if kind == "attn_local" else max_len
            total += 2 * batch * size * cfg.num_kv_heads * hd * bpe
    if cfg.family == "hybrid":
        total += n_full * 2 * batch * max_len * cfg.num_kv_heads * hd * bpe
    return total


def param_bytes(cfg: ModelConfig) -> int:
    bpe = 2 if cfg.param_dtype == "bfloat16" else 4
    return cfg.approx_params() * bpe


def make_cache(cfg: ModelConfig, batch: int, max_len: int):
    return api.init_cache(cfg, batch, max_len)


def measured_cache_bytes(cache) -> int:
    return int(sum(x.size * x.dtype.itemsize
                   for x in jax.tree_util.tree_leaves(cache)))
