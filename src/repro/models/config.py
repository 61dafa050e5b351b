"""Unified model configuration covering all assigned architecture families.

One ``ModelConfig`` describes any member of the model pool M: dense decoders
(llama/gemma/granite), MoE decoders (granite-moe, grok-1), SSM (mamba2),
hybrid SSM+attention (zamba2), hybrid SSM+attention with an MoE after every
mixer (granite-4.0-h), encoder-decoder audio backbones (whisper) and VLM
decoders with a stubbed patch frontend (internvl2).

Configs are frozen dataclasses so they can be hashed into jit static args.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import List, Tuple


@dataclass(frozen=True)
class ModelConfig:
    # -- identity ----------------------------------------------------------
    name: str
    family: str  # "dense" | "moe" | "ssm" | "hybrid" | "hybrid_moe" |
    #              "audio" | "vlm"

    # -- core transformer dims --------------------------------------------
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0  # 0 -> d_model // num_heads

    # -- attention pattern --------------------------------------------------
    attn_pattern: str = "global"  # "global" | "local_global"
    local_window: int = 4096
    # layers per pattern period; e.g. gemma2 = (1 local, 1 global) -> (1, 1),
    # gemma3 = 5 local : 1 global -> (5, 1)
    local_global_ratio: Tuple[int, int] = (1, 1)
    attn_softcap: float = 0.0  # 0 disables (gemma2 uses 50.0)
    final_softcap: float = 0.0  # final-logit softcapping (gemma2 uses 30.0)
    qk_norm: bool = False  # gemma3-style per-head RMS norm of q/k

    position_embedding: str = "rope"  # "rope" | "nope" (no rotary on q/k)
    attn_scale: float = 0.0  # score scale; 0 -> head_dim ** -0.5

    # -- MoE -----------------------------------------------------------------
    num_experts: int = 0  # experts the router routes over
    num_experts_per_tok: int = 0
    moe_d_ff: int = 0  # per-expert hidden dim; 0 -> d_ff
    # the experts held here, ``[offset, offset + held)``: this chip's share
    # of an expert-parallel layer; 0 -> all ``num_experts``
    moe_experts_held: int = 0
    moe_expert_offset: int = 0
    # the published layers form this many pipeline stages, and this config
    # is one of them; 1 -> all of them
    pipeline_stages: int = 1
    shared_d_ff: int = 0  # a shared SwiGLU expert on every token; 0 -> none

    # -- SSM (mamba2 / SSD) --------------------------------------------------
    ssm_state: int = 0
    ssm_head_dim: int = 64
    ssm_expand: int = 2
    ssm_conv_width: int = 4
    ssm_chunk: int = 256
    ssm_groups: int = 1

    # -- hybrid (zamba2): shared attention block every N ssm layers ----------
    hybrid_attn_every: int = 0

    # -- hybrid_moe (granite-4.0-h): the kinds of one period's layers
    # ("mamba" | "attn_global"), each with weights of its own and followed
    # by the FFN ----------------------------------------------------------
    layer_kinds: Tuple[str, ...] = ()

    # -- encoder-decoder (whisper backbone; conv frontend is a stub) ---------
    encoder_layers: int = 0
    encoder_seq_len: int = 1500  # whisper audio frame count after conv stub

    # -- vlm (internvl2): stubbed ViT patch-embedding prefix ------------------
    num_patches: int = 0
    vit_dim: int = 0  # stub patch-embedding dim; 0 -> d_model (no projection)

    # -- family quirks --------------------------------------------------------
    scale_embeddings: bool = False  # gemma: embeddings * sqrt(d_model)
    post_norms: bool = False        # gemma2/3: extra norm after attn/mlp
    embedding_multiplier: float = 1.0  # granite: embeddings * this
    residual_multiplier: float = 1.0   # granite: each residual branch * this
    logits_scaling: float = 1.0        # granite: logits / this

    # -- misc -----------------------------------------------------------------
    rope_theta: float = 10000.0
    norm_eps: float = 1e-6
    tie_embeddings: bool = True
    dtype: str = "bfloat16"        # activation/compute dtype
    param_dtype: str = "bfloat16"  # parameter dtype
    use_pallas: bool = False       # route hot ops through Pallas kernels
    max_seq_len: int = 1 << 19

    # ------------------------------------------------------------------ api --
    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim if self.head_dim else self.d_model // self.num_heads

    @property
    def is_encoder_decoder(self) -> bool:
        return self.encoder_layers > 0

    @property
    def is_moe(self) -> bool:
        return self.num_experts > 0

    @property
    def resolved_moe_d_ff(self) -> int:
        return self.moe_d_ff if self.moe_d_ff else self.d_ff

    @property
    def experts_held(self) -> int:
        return self.moe_experts_held or self.num_experts

    @property
    def resolved_attn_scale(self) -> float:
        return self.attn_scale or self.resolved_head_dim ** -0.5

    @property
    def has_ssm(self) -> bool:
        return self.family in ("ssm", "hybrid", "hybrid_moe")

    @property
    def layer_types(self) -> List[str]:
        """Each layer's mixer in order, named as Hugging Face's
        ``layer_types`` names them (``layer_kinds`` configs only)."""
        kinds = self.layer_kinds * (self.num_layers
                                    // max(len(self.layer_kinds), 1))
        return ["mamba" if k == "mamba" else "attention" for k in kinds]

    @property
    def sub_quadratic(self) -> bool:
        """True if the arch can serve 500k-token contexts without a full
        quadratic attention pass (SSM, hybrid, or sliding-window local)."""
        if self.has_ssm:
            return True
        return self.attn_pattern == "local_global"

    @property
    def has_decode_step(self) -> bool:
        """Encoder-only archs have no decode; all assigned archs decode."""
        return True

    # -- SSM derived dims -----------------------------------------------------
    @property
    def ssm_d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def ssm_nheads(self) -> int:
        return self.ssm_d_inner // self.ssm_head_dim

    @property
    def ssm_conv_dim(self) -> int:
        return self.ssm_d_inner + 2 * self.ssm_groups * self.ssm_state

    @property
    def ssm_in_proj_dim(self) -> int:
        # z, x, B, C, dt
        return (2 * self.ssm_d_inner + 2 * self.ssm_groups * self.ssm_state
                + self.ssm_nheads)

    # ------------------------------------------------------------------------
    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    def deployment(self) -> "ModelConfig":
        """The whole published model this config is one chip's share of:
        every pipeline stage's layers and every expert (itself when it
        holds all of them). Prices and parameter counts of the served
        model come from here."""
        return self.replace(num_layers=self.num_layers * self.pipeline_stages,
                            pipeline_stages=1, moe_experts_held=0,
                            moe_expert_offset=0)

    def reduced(self) -> "ModelConfig":
        """A small same-family config for CPU smoke tests."""
        kw = dict(
            name=self.name + "-smoke",
            num_layers=min(self.num_layers, 2),
            d_model=128,
            num_heads=4,
            num_kv_heads=min(self.num_kv_heads, 2),
            head_dim=32,
            d_ff=256,
            vocab_size=512,
            local_window=16,
            max_seq_len=256,
        )
        if self.is_moe:
            kw.update(num_experts=4, num_experts_per_tok=2, moe_d_ff=64)
        if self.has_ssm:
            kw.update(ssm_state=16, ssm_head_dim=16, ssm_chunk=16)
        if self.family == "hybrid":
            kw.update(hybrid_attn_every=1, num_layers=2)
        if self.layer_kinds:
            # two periods of one Mamba2 and one attention layer; the router
            # routes over 8 experts, this share holds half of them, and a
            # token chooses as many, so the share takes the dense layout
            # as the published one does
            kw.update(layer_kinds=("mamba", "attn_global"), num_layers=4,
                      num_experts=8, num_experts_per_tok=4,
                      moe_experts_held=4, moe_expert_offset=0,
                      shared_d_ff=96)
        if self.is_encoder_decoder:
            kw.update(encoder_layers=2, encoder_seq_len=16)
        if self.family == "vlm":
            kw.update(num_patches=4)
        return self.replace(**kw)

    def approx_params(self) -> int:
        """Approximate parameter count N (for 6*N*D model-FLOPs estimates)."""
        D, F, V, L = self.d_model, self.d_ff, self.vocab_size, self.num_layers
        H, K, Hd = self.num_heads, self.num_kv_heads, self.resolved_head_dim
        attn = D * H * Hd + 2 * D * K * Hd + H * Hd * D
        if self.is_moe:
            Fe = self.resolved_moe_d_ff
            mlp = (self.experts_held * 3 * D * Fe + D * self.num_experts
                   + 3 * D * self.shared_d_ff)
        else:
            mlp = 3 * D * F
        ssm = 0
        if self.has_ssm:
            ssm = (D * self.ssm_in_proj_dim
                   + self.ssm_conv_width * self.ssm_conv_dim
                   + self.ssm_d_inner * D + 3 * self.ssm_nheads
                   + self.ssm_d_inner)
        emb = V * D * (1 if self.tie_embeddings else 2)
        if self.family == "ssm":
            per_layer = ssm + 2 * D
        elif self.family == "hybrid":
            # shared attention block weights are counted once
            return (L * (ssm + 2 * D) + attn + mlp + 4 * D + emb)
        elif self.layer_kinds:
            # every layer is a mixer and an FFN, each with its norm
            n_attn = L * self.layer_kinds.count("attn_global") \
                // len(self.layer_kinds)
            return (n_attn * attn + (L - n_attn) * ssm
                    + L * (mlp + 2 * D) + emb + D)
        else:
            per_layer = attn + mlp + 2 * D
        total = L * per_layer + emb + D
        if self.is_encoder_decoder:
            # encoder layers + decoder cross-attention
            total += self.encoder_layers * (attn + 3 * D * F + 2 * D)
            total += L * (attn + D)  # cross-attn blocks
        return int(total)

    def active_params(self) -> int:
        """Active parameters per token (MoE: only top-k experts count; of a
        share, those of its experts a token reaches in expectation under
        uniform routing, ``k * held / num_experts``)."""
        if not self.is_moe:
            return self.approx_params()
        D, L = self.d_model, self.num_layers
        Fe = self.resolved_moe_d_ff
        dense = self.approx_params() - L * self.experts_held * 3 * D * Fe
        active = self.num_experts_per_tok * self.experts_held \
            / self.num_experts
        return int(dense + L * active * 3 * D * Fe)


def layer_is_local(cfg: ModelConfig, layer_idx: int) -> bool:
    """Static per-layer attention pattern: True -> sliding-window local."""
    if cfg.attn_pattern != "local_global":
        return False
    n_local, n_global = cfg.local_global_ratio
    period = n_local + n_global
    return (layer_idx % period) < n_local
