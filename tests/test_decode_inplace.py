"""The decode step's in-place update of the stacked caches changes no bit.

``transformer.decode_step`` carries the stacked per-period caches through
the layer scan and writes each period's slice back in place. The
reference below is the earlier formulation: the caches as the scan's
``xs`` and the new caches collected as its ``ys``. Both run the same
period body, so logits and caches must agree to the last bit.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import api
from repro.models import layers as L
from repro.models import transformer as T

ARCHS = ["mamba2-370m", "granite-4.0-h-small", "zamba2-2.7b", "gemma2-9b",
         "llama3.2-1b", "granite-moe-1b-a400m"]
B, PROMPT, MAX_LEN, STEPS = 2, 6, 16, 4


def _decode_step_xs_ys(params, cfg, token, cache):
    """The period scan with the caches as ``xs`` and the new ones as ``ys``."""
    pattern, n_full, tail = T.layout(cfg)
    x = T._embed_inputs(params, cfg, token)
    cache_len = cache["len"]

    def period_body(x, xs):
        slot_params, slot_caches = xs
        new_caches = {}
        for i, kind in enumerate(pattern):
            p = slot_params[f"slot{i}"]
            lc = slot_caches[f"slot{i}"]
            if kind == "mamba":
                x, new_caches[f"slot{i}"] = T._apply_mamba_layer_decode(
                    p, cfg, x, lc)
            else:
                ring = (kind == "attn_local"
                        and lc["k"].shape[1] == cfg.local_window)
                x, new_caches[f"slot{i}"] = T._apply_attn_layer_decode(
                    p, cfg, x, lc, cache_len, T.slot_window(cfg, kind), ring)
        if cfg.family == "hybrid":
            x, new_caches["shared"] = T._apply_attn_layer_decode(
                params["shared"], cfg, x, slot_caches["shared"], cache_len,
                None, False)
        return x, new_caches

    new_stacked = {}
    if n_full > 0:
        scan_caches = dict(cache["slots"])
        if cfg.family == "hybrid":
            scan_caches["shared"] = cache["shared"]
        x, new_stacked = jax.lax.scan(period_body, x,
                                      (params["layers"], scan_caches),
                                      length=n_full)

    new_tail = []
    for j, kind in enumerate(tail):
        p, lc = params["tail"][j], cache["tail"][j]
        if kind == "mamba":
            x, st = T._apply_mamba_layer_decode(p, cfg, x, lc)
        else:
            ring = kind == "attn_local" and lc["k"].shape[1] == cfg.local_window
            x, st = T._apply_attn_layer_decode(
                p, cfg, x, lc, cache_len, T.slot_window(cfg, kind), ring)
        new_tail.append(st)

    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    new_cache = {
        "len": cache_len + 1,
        "slots": {k: v for k, v in new_stacked.items() if k != "shared"},
        "tail": new_tail,
    }
    if "shared" in new_stacked:
        new_cache["shared"] = new_stacked["shared"]
    return L.unembed(params["embed"], cfg, x), new_cache


def _bits(tree):
    return [(a.dtype, np.asarray(a).reshape(-1).view(np.uint8))
            for a in jax.tree.leaves(tree)]


@pytest.mark.parametrize("arch", ARCHS)
def test_in_place_decode_matches_the_xs_ys_scan(arch):
    cfg = get_config(arch, reduced=True)
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(1), (B, PROMPT), 0,
                                cfg.vocab_size, jnp.int32)
    _, cache = api.prefill(params, cfg, MAX_LEN, tokens=prompt)
    assert jax.tree.leaves(cache["slots"]), "no stacked cache to update"

    step = jax.jit(lambda p, t, c: api.decode_step(p, cfg, t, c))
    ref_step = jax.jit(lambda p, t, c: _decode_step_xs_ys(p, cfg, t, c))
    token = prompt[:, -1:]
    got, want = cache, cache
    for _ in range(STEPS):
        got_logits, got = step(params, token, got)
        want_logits, want = ref_step(params, token, want)
        assert jax.tree.structure(got) == jax.tree.structure(want)
        for (dt, a), (want_dt, b) in zip(_bits((got_logits, got)),
                                         _bits((want_logits, want))):
            assert dt == want_dt
            np.testing.assert_array_equal(a, b)
        token = jnp.argmax(want_logits[:, -1], axis=-1)[:, None].astype(
            jnp.int32)
