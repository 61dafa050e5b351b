"""Family ``granitemoehybrid``: Granite 4.0-H (granite-4.0-h-small).

Layers follow ``layer_types``, a repeating period of Mamba2 and attention
mixers. Each layer, with ``m`` the residual multiplier and RMS norms that
scale by ``(1 + w)``:

    h   = x + m * mixer(norm(x))
    out = h + m * (routed(norm(h)) + shared(norm(h)))

- the Mamba2 mixer is ``_mamba2.mixer``;
- attention is causal grouped-query attention with no position encoding
  (NoPE), scores scaled by ``attention_multiplier``, in one pass;
- ``routed``: a router over all ``router_experts`` experts takes each
  token's top ``num_experts_per_tok`` logits and gates them by a softmax
  over those; of the ``num_local_experts`` experts held here (from
  ``first_expert_held``), each SwiGLU runs on every token, weighted by the
  token's gate for it (0 where the token did not choose it): densely,
  whichever layout the program takes;
- ``shared``: a SwiGLU of width ``shared_intermediate_size`` on every
  token.

The embedding (tied with the output head) is scaled by
``embedding_multiplier``; the logits are divided by ``logits_scaling``.
(Equations: Hugging Face ``transformers``,
``models/granitemoehybrid/modeling_granitemoehybrid.py``.)

Weights are held as served (bf16, norms and the router float32) and every
matrix product goes through ``ar.mm``, which casts one layer's operands at
a time, so the reference fits beside the served weights' size alone.
"""

from __future__ import annotations

from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip.families import _mamba2 as M

#: configuration-file key -> the served program's ModelConfig attribute,
#: checked equal before a run so that the file is what is served
PROGRAM_KEYS = {
    "num_hidden_layers": "num_layers", "hidden_size": "d_model",
    "vocab_size": "vocab_size", "layer_types": "layer_types",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "attention_multiplier": "attn_scale",
    "position_embedding_type": "position_embedding",
    "intermediate_size": "moe_d_ff",
    "shared_intermediate_size": "shared_d_ff",
    "num_experts_per_tok": "num_experts_per_tok",
    "router_experts": "num_experts", "num_local_experts": "experts_held",
    "first_expert_held": "moe_expert_offset",
    "pipeline_stages": "pipeline_stages",
    "mamba_d_state": "ssm_state", "mamba_d_head": "ssm_head_dim",
    "mamba_n_heads": "ssm_nheads", "mamba_expand": "ssm_expand",
    "mamba_d_conv": "ssm_conv_width", "mamba_n_groups": "ssm_groups",
    "mamba_chunk_size": "ssm_chunk", "rms_norm_eps": "norm_eps",
    "embedding_multiplier": "embedding_multiplier",
    "residual_multiplier": "residual_multiplier",
    "logits_scaling": "logits_scaling",
    "tie_word_embeddings": "tie_embeddings", "dtype": "dtype",
    "param_dtype": "param_dtype",
}


def mamba_sizes(c) -> Dict:
    """The Mamba2 mixer's sizes under ``_mamba2``'s names."""
    return {"hidden_size": c["hidden_size"], "expand": c["mamba_expand"],
            "head_dim": c["mamba_d_head"], "state_size": c["mamba_d_state"],
            "n_groups": c["mamba_n_groups"], "conv_kernel": c["mamba_d_conv"],
            "norm_eps": c["rms_norm_eps"],
            "ssm_state_dtype": c["ssm_state_dtype"],
            "conv_state_dtype": c["conv_state_dtype"]}


def period(c) -> List[str]:
    """The shortest prefix of ``layer_types`` that repeats to all of it:
    the layers the served program scans over as one period."""
    kinds = list(c["layer_types"])
    for p in range(1, len(kinds) + 1):
        if len(kinds) % p == 0 and kinds == kinds[:p] * (len(kinds) // p):
            return kinds[:p]
    raise AssertionError("unreachable")


def head_dim(c) -> int:
    return c["hidden_size"] // c["num_attention_heads"]


# --------------------------------------------------------------------------
# weights
# --------------------------------------------------------------------------


def _dense(key, fan_in: int, fan_out: int, dtype, std=None):
    """The served program's projection initializer at ``dtype``: a normal
    truncated at two deviations, of std ``1 / sqrt(fan_in)`` unless
    given."""
    std = 1.0 / np.sqrt(fan_in) if std is None else std
    return (jax.random.truncated_normal(key, -2.0, 2.0, (fan_in, fan_out),
                                        M.F32) * std).astype(dtype)


def init_ffn(key, c):
    """The router over all experts, the held experts and the shared expert,
    from the program's key: expert ``i`` of a projection from
    ``fold_in(k, i)``, the down projection at the std of the whole layer's
    fan-in (``router_experts * intermediate_size``)."""
    d, fe, fs = (c["hidden_size"], c["intermediate_size"],
                 c["shared_intermediate_size"])
    e = c["router_experts"]
    first = c["first_expert_held"]
    k1, k2, k3, k4 = jax.random.split(key, 4)
    s1, s2, s3 = jax.random.split(jax.random.fold_in(key, 4), 3)

    def experts(k, fan_in, fan_out, std):
        ids = first + jnp.arange(c["num_local_experts"])
        return jax.vmap(lambda i: _dense(jax.random.fold_in(k, i), fan_in,
                                         fan_out, M.BF16, std))(ids)

    return {"ffn_norm": jnp.zeros((d,), M.F32),
            "router": _dense(k1, d, e, M.F32),
            "w_gate": experts(k2, d, fe, 1.0 / np.sqrt(d)),
            "w_up": experts(k3, d, fe, 1.0 / np.sqrt(d)),
            "w_down": experts(k4, fe, d, 1.0 / np.sqrt(e * fe)),
            "shared_gate": _dense(s1, d, fs, M.BF16),
            "shared_up": _dense(s2, d, fs, M.BF16),
            "shared_down": _dense(s3, fs, d, M.BF16)}


def init_attention(key, c):
    d, hd = c["hidden_size"], head_dim(c)
    h, kv = c["num_attention_heads"], c["num_key_value_heads"]
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {"pre_norm": jnp.zeros((d,), M.F32),
            "wq": _dense(k1, d, h * hd, M.BF16),
            "wk": _dense(k2, d, kv * hd, M.BF16),
            "wv": _dense(k3, d, kv * hd, M.BF16),
            "wo": _dense(k4, h * hd, d, M.BF16)}


def init_layer(key, c, kind: str):
    """One layer's weights from the key the program gives its slot."""
    if kind == "mamba":
        return {**M.init_layer(key, mamba_sizes(c)),
                **init_ffn(jax.random.fold_in(key, 1), c)}
    k1, k2 = jax.random.split(key)
    return {**init_attention(k1, c), **init_ffn(k2, c)}


def embed_table(key, c):
    """The tied table, drawn so that the embedding, once multiplied, has
    std 0.02 (the program's initializer)."""
    std = 0.02 / c["embedding_multiplier"]
    return (jax.random.normal(key, (c["vocab_size"], c["hidden_size"]),
                              M.F32) * std).astype(M.BF16)


def init_weights(key, c):
    """The served weights, from the key the program seeds with: per slot
    of the period, its layers of every period stacked."""
    kinds = period(c)
    n = c["num_hidden_layers"] // len(kinds)
    keys = jax.random.split(key, 8)
    slots = []
    for i, kind in enumerate(kinds):
        slot_keys = jax.random.split(jax.random.fold_in(keys[1], i), n)
        slots.append(jax.vmap(lambda k, kind=kind: init_layer(k, c, kind))(
            slot_keys))
    return {"embed": embed_table(keys[0], c),
            "final_norm": jnp.zeros((c["hidden_size"],), M.F32),
            "slots": slots}


def from_program(params, c):
    """The served program's parameter tree under the reference's names:
    the tests check it equal, leaf for leaf, to ``init_weights``."""
    slots = []
    for i, kind in enumerate(period(c)):
        p = params["layers"][f"slot{i}"]
        moe = p["moe"]
        ffn = {"ffn_norm": p["norm_mlp"]["scale"], "router": moe["router"],
               "w_gate": moe["w_gate"], "w_up": moe["w_up"],
               "w_down": moe["w_down"],
               "shared_gate": moe["shared"]["w_gate"],
               "shared_up": moe["shared"]["w_up"],
               "shared_down": moe["shared"]["w_down"]}
        if kind == "mamba":
            m = p["mamba"]
            mixer = {"pre_norm": p["norm"]["scale"],
                     "in_proj": m["in_proj"], "conv_w": m["conv_w"],
                     "conv_b": m["conv_b"], "A_log": m["A_log"], "D": m["D"],
                     "dt_bias": m["dt_bias"],
                     "gate_norm": m["norm"]["scale"],
                     "out_proj": m["out_proj"]}
        else:
            mixer = {"pre_norm": p["norm_attn"]["scale"],
                     **{k: p["attn"][k] for k in ("wq", "wk", "wv", "wo")}}
        slots.append({**mixer, **ffn})
    return {"embed": params["embed"]["tokens"],
            "final_norm": params["final_norm"]["scale"], "slots": slots}


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------


def attention(p, h, c, ar: M.Arith):
    """Causal NoPE grouped-query attention from the normed input h
    (B, S, D) float32 to the output projection."""
    b, s, _ = h.shape
    nh, kv, hd = c["num_attention_heads"], c["num_key_value_heads"], \
        head_dim(c)
    q = ar.mm("bsd,de->bse", h, p["wq"], -1, 0).reshape(b, s, nh, hd)
    k = ar.mm("bsd,de->bse", h, p["wk"], -1, 0).reshape(b, s, kv, hd)
    v = ar.mm("bsd,de->bse", h, p["wv"], -1, 0).reshape(b, s, kv, hd)
    k = jnp.repeat(k, nh // kv, axis=2)
    v = jnp.repeat(v, nh // kv, axis=2)
    scores = ar.mm("bqhd,bkhd->bhqk", q, k, -1, -1) \
        * c["attention_multiplier"]
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    out = ar.mm("bhqk,bkhd->bqhd", probs, v, -1, 1).reshape(b, s, nh * hd)
    return ar.mm("bse,ed->bsd", out, p["wo"], -1, 0)


def routed_gates(logits, c):
    """(..., held): each token's gate for each held expert, from the
    router's logits over all experts."""
    top, idx = jax.lax.top_k(logits, c["num_experts_per_tok"])
    gates = jax.nn.softmax(top, axis=-1)
    ids = c["first_expert_held"] + jnp.arange(c["num_local_experts"])
    return jnp.sum(jnp.where(idx[..., None] == ids, gates[..., None], 0.0),
                   axis=-2)


def swiglu(x, w_gate, w_up, w_down, ar: M.Arith):
    return ar.mm("...f,fd->...d",
                 jax.nn.silu(ar.mm("...d,df->...f", x, w_gate, -1, 0))
                 * ar.mm("...d,df->...f", x, w_up, -1, 0), w_down, -1, 0)


def ffn(p, n, c, ar: M.Arith):
    """The held experts' part and the shared expert, from the normed input
    n (B, S, D) float32."""
    comb = routed_gates(ar.mm("bsd,de->bse", n, p["router"], -1, 0), c)
    gate = ar.mm("bsd,edf->ebsf", n, p["w_gate"], -1, 1)
    up = ar.mm("bsd,edf->ebsf", n, p["w_up"], -1, 1)
    out = ar.mm("ebsf,efd->ebsd", jax.nn.silu(gate) * up, p["w_down"], -1, 1)
    routed = jnp.einsum("ebsd,bse->bsd", out, comb, precision=M.HIGHEST)
    return routed + swiglu(n, p["shared_gate"], p["shared_up"],
                           p["shared_down"], ar)


def layer(p, x, c, kind: str, ar: M.Arith):
    eps, m = c["rms_norm_eps"], c["residual_multiplier"]
    h = M.rmsnorm(x, p["pre_norm"], eps)
    h = M.mixer(p, h, mamba_sizes(c), ar) if kind == "mamba" \
        else attention(p, h, c, ar)
    x = x + m * h
    return x + m * ffn(p, M.rmsnorm(x, p["ffn_norm"], eps), c, ar)


def logits(w, tokens, c, ar: M.Arith, start: int):
    """Logits at positions ``start:`` of ``tokens`` (B, S), float32."""
    kinds = period(c)
    x = w["embed"][tokens].astype(M.F32) * c["embedding_multiplier"]

    def one_period(x, slots):
        for kind, p in zip(kinds, slots):
            x = layer(p, x, c, kind, ar)
        return x, None

    x, _ = jax.lax.scan(one_period, x, w["slots"])
    x = M.rmsnorm(x[:, start:], w["final_norm"], c["rms_norm_eps"])
    return M.unembed(w["embed"], x, ar) / c["logits_scaling"]


# --------------------------------------------------------------------------
# counts
# --------------------------------------------------------------------------


def _layer_counts(c) -> Dict[str, int]:
    """Per layer kind, what the counts below add up."""
    d, hd = c["hidden_size"], head_dim(c)
    nh, kv = c["num_attention_heads"], c["num_key_value_heads"]
    fe, fs = c["intermediate_size"], c["shared_intermediate_size"]
    attn_w = d * (nh + 2 * kv) * hd + nh * hd * d
    return {
        "attn_bytes": 2 * attn_w + 4 * d,
        "attn_flops": 2 * attn_w,
        "expert_bytes": 2 * 3 * d * fe,
        "expert_flops": 2 * 3 * d * fe,
        # router (float32), shared expert, the FFN's norm
        "ffn_bytes": 4 * d * c["router_experts"] + 2 * 3 * d * fs + 4 * d,
        "ffn_flops": 2 * d * c["router_experts"] + 2 * 3 * d * fs,
        "kv_row_bytes": 2 * kv * hd * 2,
    }


def _kinds(c) -> List[str]:
    return list(c["layer_types"])


def param_bytes(c) -> int:
    """Bytes of the served weights: projections, experts, the conv and the
    embedding in bf16; norms, the router and the per-head scalars in
    float32."""
    n = _layer_counts(c)
    mc = mamba_sizes(c)
    kinds = _kinds(c)
    mixers = sum(M.layer_param_bytes(mc) if k == "mamba"
                 else n["attn_bytes"] for k in kinds)
    ffns = len(kinds) * (n["ffn_bytes"]
                         + c["num_local_experts"] * n["expert_bytes"])
    d, v = c["hidden_size"], c["vocab_size"]
    return mixers + ffns + 2 * v * d + 4 * d


def experts_touched(c, n_active: int) -> float:
    """The held experts that ``n_active`` tokens choose in expectation
    under uniform routing: each of the held misses a token with chance
    ``1 - k / router_experts``."""
    miss = 1.0 - c["num_experts_per_tok"] / c["router_experts"]
    return c["num_local_experts"] * (1.0 - miss ** n_active)


def slot_state_bytes(c, kv_len: int) -> int:
    """Bytes one decode slot keeps: each Mamba2 layer's state and conv
    tail, and each attention layer's ``kv_len`` rows of K and V."""
    kinds = _kinds(c)
    return (kinds.count("mamba") * M.slot_state_bytes(mamba_sizes(c))
            + kinds.count("attention") * kv_len
            * _layer_counts(c)["kv_row_bytes"])


def decode_cost(c, n_active: int, kv_len: int):
    """(operations, bytes) one decode step needs with ``n_active`` slots in
    use at ``kv_len`` cached positions: every weight read once, except the
    held experts no active token chose; each active slot's state read and
    written (an attention layer reads its ``kv_len`` rows and writes one).
    A token's routed operations are those of the ``k * held / experts``
    held experts it chooses in expectation."""
    n = _layer_counts(c)
    kinds = _kinds(c)
    d, v = c["hidden_size"], c["vocab_size"]
    held, k, e = (c["num_local_experts"], c["num_experts_per_tok"],
                  c["router_experts"])
    nh, hd = c["num_attention_heads"], head_dim(c)
    n_mamba, n_attn = kinds.count("mamba"), kinds.count("attention")
    per_token = (n_mamba * M.token_flops(mamba_sizes(c))
                 + n_attn * (n["attn_flops"] + 4 * nh * hd * kv_len)
                 + len(kinds) * (n["ffn_flops"]
                                 + k * held / e * n["expert_flops"])
                 + 2 * d * v)
    untouched = held - experts_touched(c, n_active)
    weights = param_bytes(c) - len(kinds) * untouched * n["expert_bytes"]
    state = (n_mamba * 2 * M.slot_state_bytes(mamba_sizes(c))
             + n_attn * (kv_len + 1) * n["kv_row_bytes"])
    return n_active * per_token, weights + n_active * state


def prefill_flops(c, prompt_len: int) -> int:
    """Operations of a prompt's prefill (causal attention over the
    positions before each, routed experts as at decode); logits at its
    last position."""
    n = _layer_counts(c)
    kinds = _kinds(c)
    nh, hd = c["num_attention_heads"], head_dim(c)
    held, k, e = (c["num_local_experts"], c["num_experts_per_tok"],
                  c["router_experts"])
    per_token = (kinds.count("mamba") * M.token_flops(mamba_sizes(c))
                 + kinds.count("attention") * n["attn_flops"]
                 + len(kinds) * (n["ffn_flops"]
                                 + k * held / e * n["expert_flops"]))
    attend = kinds.count("attention") * 4 * nh * hd \
        * prompt_len * (prompt_len + 1) // 2
    return int(prompt_len * per_token + attend
               + 2 * c["hidden_size"] * c["vocab_size"])
