"""granite-4.0-h-small's plain float32 reference against the served
program at the program's smoke size, on the CPU: prefill and then decoding
through the batcher's cache give the reference's logits; the shares of an
expert-parallel layer add up to the uncut layer; and the counts the
roofline metrics divide by are the served arrays' sizes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_helpers import config_file, reduced_sizes
from benchmarks.chip.families import _mamba2 as M
from benchmarks.chip.families import granitemoehybrid as G

CELL = "granite-4.0-h-small.extract-batch"


def _sizes(**kw):
    return dict(reduced_sizes(config_file(CELL)), **kw)


def _program(sizes, seed, **kw):
    from repro.configs import get_config
    from repro.models import api

    cfg = get_config(sizes["name"], reduced=True).replace(**kw)
    return cfg, api.init_params(jax.random.PRNGKey(seed), cfg)


def test_prefill_then_decode_through_the_batcher_cache():
    """Two requests admitted by the batcher's jitted prefill and splice
    into a two-slot cache that holds KV and SSM state side by side, then
    decoded through that cache, teacher-forced, in float32: at every
    position the reference's one full pass gives the same logits.

    Tolerance: the program at float32 and the reference at "highest" do
    the same arithmetic in another order (the chunked SSD scan against the
    per-position recurrence, the cached one-query attention against the
    one-pass causal attention), which moved a logit by 4.2e-9 here; the
    served bf16 program, on the same path, misses by 9.9e-5. The logits
    are divided by 16 and span about 0.009. 2e-6 lies between the two
    readings with a factor of 50 or more on each side."""
    from repro.models import api
    from repro.serving import scheduler as sched

    sizes = _sizes()
    seed = 2**31 + 29
    cfg, params = _program(sizes, seed)
    cfg = cfg.replace(dtype="float32", param_dtype="float32")
    params = jax.tree.map(lambda x: x.astype(jnp.float32), params)
    prompt_len, new = sched.PREFILL_BUCKET, 12
    toks = np.random.default_rng(4).integers(
        3, sizes["vocab_size"], size=(2, prompt_len + new)).astype(np.int32)

    b = sched.ContinuousBatcher(params, cfg, num_slots=2,
                                max_len=prompt_len + new + 8, eos_id=-1)
    for slot in range(2):
        tok, cache1 = b._prefill(params, toks[slot:slot + 1, :prompt_len],
                                 np.int32(prompt_len))
        b.cache, b.tokens = b._splice(b.cache, cache1, b.tokens,
                                      np.int32(slot), tok)
    cache = dict(b.cache, len=jnp.asarray(prompt_len, jnp.int32))
    got = []
    with jax.default_matmul_precision("highest"):
        for t in range(prompt_len, prompt_len + new):
            lg, cache = api.decode_step(params, cfg,
                                        jnp.asarray(toks[:, t:t + 1]), cache)
            got.append(np.asarray(lg[:, 0]))
        ref = G.logits(G.init_weights(jax.random.PRNGKey(seed), sizes),
                       jnp.asarray(toks), sizes, M.Arith("f32"), prompt_len)
    got = np.stack(got, axis=1)
    ref = np.asarray(ref)
    assert np.ptp(ref) > 2e-3
    np.testing.assert_allclose(got, ref, rtol=0, atol=2e-6)


@pytest.mark.parametrize("shares", [2, 4])
def test_expert_shares_sum_to_the_uncut_layer(shares):
    """The router routes over 8 experts; each of ``shares`` chips holds
    its 8 / shares of them. The program's layer on each share, with the
    shared expert (which every chip computes alike) counted once, adds up
    to the reference's layer holding all 8. Float32 both: the output lies
    within about 2.1 and the sums differ by 2.4e-7 (2 shares) and 7.2e-7
    (4), the ulps of adding shares in another order; 2e-5 leaves 28 times
    that, and a share left out or counted twice moves the sum by 1e-3 or
    more (checked below for each share)."""
    from repro.models import moe

    sizes = _sizes()
    e = sizes["router_experts"]
    held = e // shares
    cfg, _ = _program(sizes, 0, dtype="float32")
    key = jax.random.PRNGKey(2**31 + 43)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 24, cfg.d_model))
    parts = []
    for i in range(shares):
        share = cfg.replace(moe_experts_held=held, moe_expert_offset=i * held)
        p = jax.tree.map(lambda a: a.astype(jnp.float32),
                         moe.init_moe(key, share))
        with jax.default_matmul_precision("highest"):
            y, _ = moe.moe_ffn(p, share, x)
        parts.append(np.asarray(y))
    shared = _shared_out(key, cfg, x)
    total = sum(parts) - (shares - 1) * shared

    uncut = _sizes(num_local_experts=e, first_expert_held=0)
    w = G.init_ffn(key, uncut)
    with jax.default_matmul_precision("highest"):
        want = G.ffn(w, x, uncut, M.Arith("f32"))
    assert all(np.abs(p - shared).max() > 1e-3 for p in parts)
    np.testing.assert_allclose(total, np.asarray(want), rtol=0, atol=2e-5)


def _shared_out(key, cfg, x):
    from repro.models import layers as L
    from repro.models import moe

    p = moe.init_moe(key, cfg)["shared"]
    p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
    with jax.default_matmul_precision("highest"):
        return np.asarray(L.mlp(p, x))


def test_counted_bytes_match_the_served_arrays():
    """``param_bytes`` is the size of the program's weights, and one
    slot's state at ``kv_len`` cached positions the size of its cache at
    that length (with the cache's int32 length)."""
    from repro.configs import get_config
    from repro.models import api

    sizes = _sizes()
    cfg = get_config(sizes["name"], reduced=True)
    params = jax.eval_shape(lambda: api.init_params(jax.random.PRNGKey(0),
                                                    cfg))
    cache = jax.eval_shape(lambda: api.init_cache(cfg, 1, 10))
    size = lambda tree: sum(int(np.prod(x.shape)) * x.dtype.itemsize
                            for x in jax.tree.leaves(tree))
    assert G.param_bytes(sizes) == size(params)
    assert G.slot_state_bytes(sizes, 10) + 4 == size(cache)


def test_decode_cost_counts_the_experts_tokens_touch():
    """With one active token, the held experts it reaches in expectation
    are ``k * held / experts``; with many, all of them; and the bytes of
    a step lie between the weights without any expert and all weights."""
    c = _sizes()
    held, k, e = (c["num_local_experts"], c["num_experts_per_tok"],
                  c["router_experts"])
    assert G.experts_touched(c, 1) == pytest.approx(k * held / e)
    assert G.experts_touched(c, 10_000) == pytest.approx(held)
    expert = 2 * 3 * c["hidden_size"] * c["intermediate_size"]
    layers = c["num_hidden_layers"]
    cost = lambda n: G.decode_cost(c, n, 0)[1]
    # one slot's state, read and written: the step from n to n + 1 slots
    # once every held expert is touched
    slot = cost(10_001) - cost(10_000)
    weights = G.param_bytes(c)
    assert cost(1) - slot == pytest.approx(
        weights - layers * (held - k * held / e) * expert)
    assert cost(10_000) - 10_000 * slot == pytest.approx(weights)
