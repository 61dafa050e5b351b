"""Architecture registry: the model pool M (the 10 assigned architectures
and granite-4.0-h-small).

``get_config(name)`` returns the full production config;
``get_config(name, reduced=True)`` a small same-family smoke config.
"""

from __future__ import annotations

from typing import Dict, List

from repro.models.config import ModelConfig

from repro.configs.granite_moe_1b import CONFIG as granite_moe_1b
from repro.configs.grok_1_314b import CONFIG as grok_1_314b
from repro.configs.whisper_medium import CONFIG as whisper_medium
from repro.configs.gemma2_9b import CONFIG as gemma2_9b
from repro.configs.llama32_1b import CONFIG as llama32_1b
from repro.configs.gemma3_27b import CONFIG as gemma3_27b
from repro.configs.granite_34b import CONFIG as granite_34b
from repro.configs.mamba2_370m import CONFIG as mamba2_370m
from repro.configs.zamba2_27b import CONFIG as zamba2_27b
from repro.configs.internvl2_1b import CONFIG as internvl2_1b
from repro.configs.granite_4_0_h_small import CONFIG as granite_4_0_h_small

ARCHS: Dict[str, ModelConfig] = {
    c.name: c
    for c in [
        granite_moe_1b, grok_1_314b, whisper_medium, gemma2_9b, llama32_1b,
        gemma3_27b, granite_34b, mamba2_370m, zamba2_27b, internvl2_1b,
        granite_4_0_h_small,
    ]
}


def list_archs() -> List[str]:
    return list(ARCHS.keys())


def get_config(name: str, reduced: bool = False) -> ModelConfig:
    if name not in ARCHS:
        raise KeyError(f"unknown arch {name!r}; available: {list_archs()}")
    cfg = ARCHS[name]
    return cfg.reduced() if reduced else cfg
