"""Each family's plain float32 reference agrees with the served program
at the program's smoke size, on the CPU: the weights it draws from a seed
are the program's, leaf for leaf (the family's ``from_program`` names
them), and its logits are the program's full forward pass computed in
float32. Where ``data/reference_logits/<config>.json`` records them, the
reference's logits are also bit-identical to the recorded ones."""

import hashlib
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_helpers import BENCH, DATA, config_file, reduced_sizes
from benchmarks.chip import layout
from benchmarks.chip.families import _mamba2 as M

#: one cell of each configuration
CELLS = sorted({w["config"]: w["name"] for w in BENCH["workloads"]}.values())

#: recorded reference logits, one file per configuration
RECORDED = sorted(p.stem for p in (DATA / "reference_logits").glob("*.json"))


def _program(sizes, seed):
    from repro.configs import get_config
    from repro.models import api

    cfg = get_config(sizes["name"], reduced=True)
    return cfg, api.init_params(jax.random.PRNGKey(seed), cfg)


@pytest.mark.parametrize("cell", CELLS)
def test_reference_weights_are_the_programs(cell):
    sizes = reduced_sizes(config_file(cell))
    fam = layout.family(sizes["family"])
    seed = 2**31 + 17
    _, params = _program(sizes, seed)
    ref = fam.init_weights(jax.random.PRNGKey(seed), sizes)
    want = fam.from_program(params, sizes)
    # every leaf of the program's tree, each once: nothing left over
    assert sorted(map(id, jax.tree.leaves(want))) \
        == sorted(map(id, jax.tree.leaves(params)))
    assert jax.tree.structure(ref) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(ref), jax.tree.leaves(want)):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


@pytest.mark.parametrize("config", RECORDED)
def test_reference_logits_are_the_recorded_ones(config):
    with open(DATA / "reference_logits" / f"{config}.json") as f:
        rec = json.load(f)
    sizes = reduced_sizes(layout.config(BENCH, config))
    fam = layout.family(sizes["family"])
    toks = np.random.default_rng(rec["tokens_seed"]).integers(
        3, sizes["vocab_size"], size=rec["tokens_shape"]).astype(np.int32)
    w = fam.init_weights(jax.random.PRNGKey(rec["seed"]), sizes)
    for mode, want in rec["sha256"].items():
        with jax.default_matmul_precision("highest"):
            lg = fam.logits(w, jnp.asarray(toks), sizes, M.Arith(mode), 0)
        got = np.asarray(lg, np.float32)
        assert hashlib.sha256(got.tobytes()).hexdigest() == want, mode


@pytest.mark.parametrize("cell", CELLS)
def test_reference_logits_match_program_forward_in_float32(cell):
    from repro.models import api

    sizes = reduced_sizes(config_file(cell))
    fam = layout.family(sizes["family"])
    cfg, params = _program(sizes, 7)
    cfg32 = cfg.replace(dtype="float32", param_dtype="float32")
    params32 = jax.tree.map(lambda x: x.astype(jnp.float32), params)
    toks = np.random.default_rng(0).integers(3, sizes["vocab_size"],
                                             size=(2, 40)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        got, _ = api.forward(params32, cfg32, tokens=jnp.asarray(toks))
        ref = fam.logits(fam.init_weights(jax.random.PRNGKey(7), sizes),
                         jnp.asarray(toks), sizes, M.Arith("f32"), 0)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(got),
                               rtol=1e-4, atol=1e-4)


def test_fp8_arithmetic_rounds_each_product():
    # the row's absmax 3.0 maps to e4m3's 448; 2.9 maps to 433.07, between
    # the neighbours 416 and 448 (3 mantissa bits), and rounds to 448
    a = jnp.asarray([[3.0, 2.9]])
    b = jnp.ones((2, 1))
    exact = M.Arith("f32").mm("ij,jk->ik", a, b, -1, 0)
    low = M.Arith("fp8").mm("ij,jk->ik", a, b, -1, 0)
    assert float(exact[0, 0]) == pytest.approx(5.9)
    assert float(low[0, 0]) == pytest.approx(6.0)
