"""granite-4.0-h at smoke size on the CPU: the expert layer drops no
token, every attention path the config can take agrees, and the compiled
decode step names its parts."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import api
from repro.models import moe as M
from repro.serving.decode import make_serve_step
from repro.serving.scheduler import bucket_len

ARCH = "granite-4.0-h-small"
SCOPES = ("mixer.mamba", "mixer.attention", "ffn.router", "ffn.experts",
          "ffn.shared")


def _cfg32():
    return get_config(ARCH, reduced=True).replace(dtype="float32",
                                                  param_dtype="float32")


def test_smoke_config_keeps_the_cut():
    cfg = get_config(ARCH, reduced=True)
    assert set(cfg.layer_kinds) == {"mamba", "attn_global"}
    assert cfg.experts_held < cfg.num_experts
    full = get_config(ARCH)
    assert full.layer_types.count("mamba") == 18
    assert full.layer_types.count("attention") == 2
    assert (full.num_experts, full.experts_held,
            full.num_experts_per_tok) == (72, 9, 10)


def test_no_token_dropped_at_the_largest_prefill_bucket(rng):
    """Every token of the largest prefill bucket the backend admits routes
    to held expert 0 (and 1): each still gets its full output, the gated
    sum of its experts and the shared expert, as a per-token loop computes
    it."""
    cfg = _cfg32()
    n = bucket_len(96)  # JaxBackend cuts prompts to 96 tokens
    d = cfg.d_model
    params = M.init_moe(rng, cfg)
    v = jax.random.normal(jax.random.PRNGKey(1), (d,))
    # logits of experts 0 and 1 follow v; the rest see only the noise
    router = jnp.zeros((d, cfg.num_experts)).at[:, 0].set(8 * v / (v @ v))
    router = router.at[:, 1].set(6 * v / (v @ v))
    params = dict(params, router=router)
    x = v + 0.05 * jax.random.normal(jax.random.PRNGKey(2), (n, d))
    out, _ = M.moe_ffn(params, cfg, x[None])
    assign, gates, _ = M.router_topk(params, cfg, x)
    assert (np.asarray(assign[:, 0]) == 0).all()

    def swiglu(w_gate, w_up, w_down, t):
        return (jax.nn.silu(t @ w_gate) * (t @ w_up)) @ w_down

    want = []
    for t in range(n):
        acc = swiglu(params["shared"]["w_gate"], params["shared"]["w_up"],
                     params["shared"]["w_down"], x[t])
        for j in range(cfg.num_experts_per_tok):
            e = int(assign[t, j]) - cfg.moe_expert_offset
            if 0 <= e < cfg.experts_held:
                acc = acc + gates[t, j] * swiglu(
                    params["w_gate"][e], params["w_up"][e],
                    params["w_down"][e], x[t])
        want.append(acc)
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_attention_paths_agree(rng):
    """NoPE and the configured scale on every path: the jnp decode
    attention and the Pallas kernels (interpreted here) give the full
    forward's logits."""
    cfg = _cfg32()
    params = api.init_params(rng, cfg)
    toks = jax.random.randint(rng, (2, 20), 0, cfg.vocab_size)
    full, _ = api.forward(params, cfg, tokens=toks)
    pallas, _ = api.forward(params, cfg.replace(use_pallas=True),
                            tokens=toks)
    np.testing.assert_allclose(np.asarray(pallas), np.asarray(full),
                               atol=2e-6)
    for c in (cfg, cfg.replace(use_pallas=True)):
        _, cache = api.prefill(params, c, 48, tokens=toks[:, :19])
        dl, _ = api.decode_step(params, c, toks[:, 19:], cache)
        np.testing.assert_allclose(np.asarray(dl[:, 0]),
                                   np.asarray(full[:, 19]), atol=2e-6)


def test_positions_do_not_enter_nope_attention(rng):
    """Without rotary embedding, a query's attention output depends on
    which keys it sees, not on where they sit: shifting every position
    changes nothing."""
    from repro.models import attention as A

    cfg = _cfg32()
    p = A.init_attention(rng, cfg)
    x = jax.random.normal(rng, (1, 12, cfg.d_model))
    pos = jnp.arange(12)[None]
    out, _ = A.attn_prefill(p, cfg, x, pos)
    shifted, _ = A.attn_prefill(p, cfg, x, pos + 1000)
    np.testing.assert_allclose(np.asarray(shifted), np.asarray(out),
                               atol=1e-6)
    rope, _ = A.attn_prefill(p, cfg.replace(position_embedding="rope"), x,
                             pos + 1000)
    assert not np.allclose(np.asarray(rope), np.asarray(out), atol=1e-3)


def test_compiled_decode_step_names_its_parts(rng):
    """The five named scopes reach the compiled step's op metadata, which
    the device trace's operations carry."""
    cfg = get_config(ARCH, reduced=True)
    params = api.init_params(rng, cfg)
    cache = api.init_cache(cfg, 2, 40)
    step = jax.jit(make_serve_step(cfg))
    text = step.lower(params, jnp.zeros((2, 1), jnp.int32),
                      cache).compile().as_text()
    missing = [s for s in SCOPES if f"/{s}/" not in text]
    assert not missing, missing


def _loop_oracle(params, cfg, x):
    """Each token through the held experts it chose, weighted by its gate,
    one token at a time."""
    assign, gates, _ = M.router_topk(params, cfg, x)
    want = []
    for t in range(x.shape[0]):
        acc = jnp.zeros((cfg.d_model,))
        for j in range(cfg.num_experts_per_tok):
            e = int(assign[t, j]) - cfg.moe_expert_offset
            if 0 <= e < cfg.experts_held:
                h = jax.nn.silu(x[t] @ params["w_gate"][e]) \
                    * (x[t] @ params["w_up"][e])
                acc = acc + gates[t, j] * (h @ params["w_down"][e])
        want.append(acc)
    return np.asarray(want)


@pytest.mark.parametrize("top_k", [2, 4])
def test_both_expert_layouts_give_each_token_its_held_experts(rng, top_k):
    """A share holding experts 4-7 of 8: the grouped layout (ragged
    matmuls over the sorted pairs) and the dense one (every held expert on
    every token) both give each token its held experts' gated sum, with
    tokens whose experts are all held elsewhere among them. Float32; the
    layouts add the same products in another order."""
    cfg = _cfg32().replace(num_experts_per_tok=top_k, moe_expert_offset=4)
    params = M.init_moe(rng, cfg)
    x = jax.random.normal(jax.random.PRNGKey(3), (40, cfg.d_model))
    assign, gates, _ = M.router_topk(params, cfg, x)
    held = (np.asarray(assign) >= 4).sum(axis=1)
    assert (held == 0).any() and (held > 0).any()
    want = _loop_oracle(params, cfg, x)
    for layout in (M._grouped, M._dense):
        got = layout(params, cfg, x, assign, gates)
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5,
                                   atol=1e-5)


def test_a_share_draws_its_experts_alone(rng):
    """Each share's experts are the uncut layer's at their ids, drawn from
    their own keys: the shares tile the uncut layer's weights exactly."""
    cfg = get_config(ARCH, reduced=True)
    whole = M.init_moe(rng, cfg.replace(moe_experts_held=0))
    held = cfg.experts_held
    for offset in range(0, cfg.num_experts, held):
        share = M.init_moe(rng, cfg.replace(moe_expert_offset=offset))
        for name in ("w_gate", "w_up", "w_down"):
            np.testing.assert_array_equal(
                np.asarray(share[name], np.float32),
                np.asarray(whole[name][offset:offset + held], np.float32))
        np.testing.assert_array_equal(np.asarray(share["router"]),
                                      np.asarray(whole["router"]))


def test_card_prices_the_published_model():
    """The optimizer's card counts the whole published model (40 layers,
    72 experts, 10 active a token: 32B-A9B), not the chip's share the
    registry entry serves."""
    from repro.core.models_catalog import analytic_price, catalog

    card = catalog()[ARCH]
    assert card.params == pytest.approx(32e9, rel=0.03)
    assert card.active_params == pytest.approx(9e9, rel=0.03)
    share = get_config(ARCH)
    whole = share.deployment()
    assert (whole.num_layers, whole.experts_held) == (40, 72)
    assert share.approx_params() < card.params / 2
    assert analytic_price(ARCH)["in"] > 0.0
