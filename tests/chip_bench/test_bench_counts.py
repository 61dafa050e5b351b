"""The operation and byte counts the roofline and utilization metrics
divide by: hand-worked numbers at a small size, and the bytes checked
against the arrays the served program really holds."""

import jax
import numpy as np

from bench_helpers import config_file, reduced_sizes
from benchmarks.chip.families import ssm

# D=4, expand 2 -> d_inner 8, head_dim 2 -> 4 heads, N=2, one group,
# conv width 4 -> conv channels 12, in_proj 2*8 + 2*2 + 4 = 24, vocab 10
TINY = {"hidden_size": 4, "expand": 2, "head_dim": 2, "state_size": 2,
        "n_groups": 1, "conv_kernel": 4, "vocab_size": 10,
        "num_hidden_layers": 1, "ssm_state_dtype": "float32",
        "conv_state_dtype": "bfloat16"}


def test_ssm_counts_by_hand():
    # per token and layer: 2*4*24 + 2*8*4 + 2*4*12 + 5*(4*2*2) = 432;
    # logits 2*4*10 = 80
    flops, nbytes = ssm.decode_cost(TINY, 3, 7)
    assert flops == 3 * (432 + 80)
    # weights: bf16 2*(4*24 + 4*12 + 12 + 8*4) = 376, f32 4*(4+3*4+8) = 96,
    # embedding 2*10*4 = 80, final norm 16; state per slot: 4*2*2*4 = 64
    # f32 SSM + 3*12*2 = 72 bf16 conv, read and written
    assert ssm.param_bytes(TINY) == 376 + 96 + 80 + 16
    assert nbytes == 568 + 3 * 2 * (64 + 72)
    assert ssm.prefill_flops(TINY, 3) == 3 * 432 + 80


def _program_bytes(name):
    from repro.configs import get_config
    from repro.models import api

    cfg = get_config(name, reduced=True)
    params = jax.eval_shape(lambda: api.init_params(
        jax.random.PRNGKey(0), cfg))
    cache = jax.eval_shape(lambda: api.init_cache(cfg, 1, 10))
    size = lambda tree: sum(int(np.prod(x.shape)) * x.dtype.itemsize
                            for x in jax.tree.leaves(tree))
    return size(params), size(cache)


def test_counted_bytes_match_the_served_arrays():
    sizes = reduced_sizes(config_file("mamba2-370m.extract-batch"))
    params, cache = _program_bytes(sizes["name"])
    assert ssm.param_bytes(sizes) == params
    # one slot's cache: its state, read and written, and the cache's int32
    # length
    _, state_bytes = ssm.decode_cost(sizes, 1, 0)
    assert cache == (state_bytes - params) // 2 + 4
