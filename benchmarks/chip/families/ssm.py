"""Family ``ssm``: a stack of Mamba2 layers (mamba2-370m).

Embedding (tied with the output head), ``num_hidden_layers`` Mamba2
layers each with its residual, a final RMS norm, logits. The layer's
equations and counts are in ``_mamba2.py``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.chip.families import _mamba2 as M

#: configuration-file key -> the served program's ModelConfig attribute,
#: checked equal before a run so that the file is what is served
PROGRAM_KEYS = {
    "num_hidden_layers": "num_layers", "hidden_size": "d_model",
    "vocab_size": "vocab_size", "state_size": "ssm_state",
    "head_dim": "ssm_head_dim", "expand": "ssm_expand",
    "conv_kernel": "ssm_conv_width", "n_groups": "ssm_groups",
    "chunk_size": "ssm_chunk", "norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings", "dtype": "dtype",
    "param_dtype": "param_dtype",
}


def init_weights(key, c):
    """The served weights, from the key the program seeds with."""
    keys = jax.random.split(key, 8)
    return {
        "embed": M.embed_table(keys[0], c),
        "final_norm": jnp.zeros((c["hidden_size"],), M.F32),
        "layers": M.init_stack(jax.random.fold_in(keys[1], 0), c,
                               c["num_hidden_layers"]),
    }


def from_program(params, c):
    """The served program's parameter tree under the reference's names:
    the tests check it equal, leaf for leaf, to ``init_weights``."""
    del c
    layer = params["layers"]["slot0"]
    m = layer["mamba"]
    return {
        "embed": params["embed"]["tokens"],
        "final_norm": params["final_norm"]["scale"],
        "layers": {"pre_norm": layer["norm"]["scale"],
                   "in_proj": m["in_proj"], "conv_w": m["conv_w"],
                   "conv_b": m["conv_b"], "A_log": m["A_log"], "D": m["D"],
                   "dt_bias": m["dt_bias"], "gate_norm": m["norm"]["scale"],
                   "out_proj": m["out_proj"]},
    }


def logits(w, tokens, c, ar: M.Arith, start: int):
    """Logits at positions ``start:`` of ``tokens`` (B, S), float32."""
    x = w["embed"][tokens].astype(M.F32)
    x, _ = jax.lax.scan(lambda x, p: (M.layer(p, x, c, ar), None), x,
                        w["layers"])
    x = M.rmsnorm(x[:, start:], w["final_norm"], c["norm_eps"])
    return M.unembed(w["embed"], x, ar)


def param_bytes(c) -> int:
    d, v = c["hidden_size"], c["vocab_size"]
    return c["num_hidden_layers"] * M.layer_param_bytes(c) + 2 * v * d \
        + 4 * d


def decode_cost(c, n_active: int, kv_len: int):
    """(operations, bytes) one decode step needs with ``n_active`` slots
    in use: every weight read once, each active slot's state read and
    written. ``kv_len`` does not matter to an SSM."""
    del kv_len
    d, v, layers = c["hidden_size"], c["vocab_size"], c["num_hidden_layers"]
    flops = n_active * (layers * M.token_flops(c) + 2 * d * v)
    nbytes = param_bytes(c) + n_active * layers * 2 * M.slot_state_bytes(c)
    return flops, nbytes


def prefill_flops(c, prompt_len: int) -> int:
    """Operations of a prompt's prefill; logits at its last position."""
    return (prompt_len * c["num_hidden_layers"] * M.token_flops(c)
            + 2 * c["hidden_size"] * c["vocab_size"])
