"""The model pool M: the registry's architectures as serving endpoints.

This is the bridge that makes the assigned architectures native to the
paper: MOAR's model-substitution directive chooses among *these* models,
and ``analytic_price`` derives their $/1M-token prices from each arch's
config-level FLOP/byte counts against the v5e peaks (chip-seconds per
token x $/chip-hour), not an API price sheet.

Assumptions (documented in DESIGN.md): $1.20 per chip-hour (v5e on-demand
ballpark), 40% prefill MFU, decode amortized over the assigned decode
batch, 1.3x HBM overhead for serving state.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Optional

from repro.configs import ARCHS
from repro.hardware import HW

CHIP_HOUR_USD = 1.20
PREFILL_MFU = 0.40
DECODE_BATCH = 128  # the assigned decode_32k batch


@dataclass(frozen=True)
class ModelCard:
    name: str
    family: str
    params: int
    active_params: int
    context_window: int
    # long-context retrieval quality in [0,1] (MRCR-style; given to agents)
    long_context_score: float
    price_in: float   # $ per 1M input tokens
    price_out: float  # $ per 1M output tokens

    def describe(self) -> str:
        return (f"{self.name}: {self.params/1e9:.1f}B params "
                f"({self.active_params/1e9:.2f}B active), ctx "
                f"{self.context_window//1024}k, in ${self.price_in:.4f}/M, "
                f"out ${self.price_out:.4f}/M, "
                f"long-ctx score {self.long_context_score:.2f}")


_CONTEXT = {
    "granite-moe-1b-a400m": 32_768,
    "grok-1-314b": 32_768,
    "whisper-medium": 8_192,
    "gemma2-9b": 131_072,
    "llama3.2-1b": 131_072,
    "gemma3-27b": 262_144,
    "granite-34b": 65_536,
    "mamba2-370m": 1_048_576,
    "zamba2-2.7b": 1_048_576,
    "internvl2-1b": 32_768,
    "granite-4.0-h-small": 131_072,
}

# MRCR-style long-context retrieval (SSMs are cheap at long ctx but lossy
# at needle retrieval; attention archs retrieve well inside their window)
_LONG_SCORE = {
    "granite-moe-1b-a400m": 0.55,
    "grok-1-314b": 0.80,
    "whisper-medium": 0.30,
    "gemma2-9b": 0.78,
    "llama3.2-1b": 0.65,
    "gemma3-27b": 0.88,
    "granite-34b": 0.72,
    "mamba2-370m": 0.40,
    "zamba2-2.7b": 0.60,
    "internvl2-1b": 0.50,
    "granite-4.0-h-small": 0.70,  # assumed: 4 attention layers in 40
}


def analytic_price(arch: str) -> Dict[str, float]:
    cfg = ARCHS[arch].deployment()
    n_act = cfg.active_params()
    n_tot = cfg.approx_params()
    # prefill: compute-bound, 2*N_active FLOPs/token at PREFILL_MFU
    chip_s_per_mtok_in = 2.0 * n_act * 1e6 / (HW["peak_flops"] * PREFILL_MFU)
    # decode: memory-bound, full weights streamed per step, amortized over
    # the decode batch; the KV-read term counts only layers that actually
    # attend over the full context (SSM: none; zamba2: its 9 shared blocks;
    # gemma local layers: a fixed window, not the running context)
    weight_bytes = n_tot * 2
    avg_ctx = 8192
    if cfg.family == "ssm":
        full_layers, window_layers = 0, 0
    elif cfg.family == "hybrid":
        full_layers = cfg.num_layers // max(cfg.hybrid_attn_every, 1)
        window_layers = 0
    elif cfg.layer_kinds:
        full_layers = cfg.layer_types.count("attention")
        window_layers = 0
    elif cfg.attn_pattern == "local_global":
        n_local, n_global = cfg.local_global_ratio
        period = n_local + n_global
        full_layers = cfg.num_layers * n_global // period
        window_layers = cfg.num_layers - full_layers
    else:
        full_layers, window_layers = cfg.num_layers, 0
    kv_row = 2 * cfg.num_kv_heads * cfg.resolved_head_dim * 2
    kv_per_tok = kv_row * (full_layers * avg_ctx
                           + window_layers * min(cfg.local_window, avg_ctx))
    chip_s_per_mtok_out = (weight_bytes / DECODE_BATCH + kv_per_tok) \
        * 1e6 / HW["hbm_bw"]
    rate = CHIP_HOUR_USD / 3600.0
    return {"in": chip_s_per_mtok_in * rate,
            "out": chip_s_per_mtok_out * rate}


_CATALOG: Optional[Dict[str, ModelCard]] = None


def catalog(refresh: bool = False) -> Dict[str, ModelCard]:
    global _CATALOG
    if _CATALOG is not None and not refresh:
        return _CATALOG
    cards = {}
    for arch, share in ARCHS.items():
        cfg = share.deployment()
        p = analytic_price(arch)
        cards[arch] = ModelCard(
            name=arch,
            family=cfg.family,
            params=cfg.approx_params(),
            active_params=cfg.active_params(),
            context_window=_CONTEXT[arch],
            long_context_score=_LONG_SCORE[arch],
            price_in=p["in"],
            price_out=p["out"],
        )
    _CATALOG = cards
    return cards


def model_names():
    return list(ARCHS.keys())


DEFAULT_MODEL = "llama3.2-1b"   # the pool's "gpt-4o-mini": small + cheap
AGENT_MODEL = "gemma3-27b"      # the pool's "gpt-5": rewrites instantiator
