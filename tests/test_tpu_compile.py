"""Compile the served path's device programs for a described TPU v5e.

Nothing runs: the TPU compiler that ships with jaxlib compiles for a
chip that is described, not attached, and refuses what the chip would
refuse (block shapes off the tiling rule, too much VMEM, a program that
does not fit HBM). Interpret-mode tests cannot see any of that.

Shapes are llama3.2-1b's published widths at ``JaxBackend``'s serving
shape: 4 decode slots x 112 cache positions (96 prompt tokens + 8 new
+ 8 slack), bf16; and the chip benchmark's two configurations at its
``extract-batch`` shape: 32 slots x 360 positions (96 + 256 + 8).

The topology is described inside a fixture, never at import: only one
process may load the TPU library at a time, and every test worker imports
this file.
"""

import os
import re

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from repro.configs import get_config  # noqa: E402

B, S = 4, 112
HBM_BYTES = 16 * 2**30  # one TPU v5e chip


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # keep libtpu's logs out of its fixed default directory under /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def llama():
    return get_config("llama3.2-1b")


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _on_chip(tree, sharding):
    return jax.tree.map(lambda s: _spec(s.shape, s.dtype, sharding), tree)


def test_flash_attention_compiles_for_v5e(one_chip, llama):
    from repro.kernels.flash_attention.ops import flash_attention
    hd = llama.resolved_head_dim
    q = _spec((B, S, llama.num_heads, hd), jnp.bfloat16, one_chip)
    kv = _spec((B, S, llama.num_kv_heads, hd), jnp.bfloat16, one_chip)
    compiled = jax.jit(
        lambda q, k, v: flash_attention(q, k, v, interpret=False)
    ).lower(q, kv, kv).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_decode_compiles_for_v5e(one_chip, llama):
    from repro.kernels.flash_decode.ops import flash_decode
    hd = llama.resolved_head_dim
    q = _spec((B, 1, llama.num_heads, hd), jnp.bfloat16, one_chip)
    kv = _spec((B, S, llama.num_kv_heads, hd), jnp.bfloat16, one_chip)
    n = _spec((), jnp.int32, one_chip)
    compiled = jax.jit(
        lambda q, k, v, n: flash_decode(q, k, v, n, interpret=False)
    ).lower(q, kv, kv, n).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_full_width_serve_step_fits_v5e(one_chip, llama):
    from repro.models import api
    from repro.serving.decode import serve_step_jit

    params = _on_chip(jax.eval_shape(
        lambda: api.init_params(jax.random.PRNGKey(0), llama)), one_chip)
    cache = _on_chip(jax.eval_shape(lambda: api.init_cache(llama, B, S)),
                     one_chip)
    token = _spec((B, 1), jnp.int32, one_chip)
    compiled = serve_step_jit(llama).lower(params, token, cache).compile()
    mem = compiled.memory_analysis()
    assert 0 < mem.argument_size_in_bytes < HBM_BYTES
    # the donated KV cache is updated in place, not copied
    assert mem.alias_size_in_bytes > 0


# HLO's names of the cache leaves' dtypes
_HLO_DTYPE = {"float32": "f32", "bfloat16": "bf16", "int8": "s8"}
# temporaries of the decode step compiled with the caches as the layer
# scan's ys, for a described v5e: 1.63 GB and 2.81 GB, a whole second
# copy of the stacked SSM state
_TEMP_LIMIT = {"mamba2-370m": 0.1e9, "granite-4.0-h-small": 1.5e9}


def _entry_copies(hlo: str):
    """Shapes ``(dtype, dims)`` of the copies in the ENTRY computation."""
    entry = hlo[hlo.index("\nENTRY "):]
    entry = entry[:entry.index("\n}")]
    return {(m.group(1), tuple(int(d) for d in m.group(2).split(",") if d))
            for m in re.finditer(
                r"= (\w+)\[([\d,]*)\]\S* copy(?:-done)?\(", entry)}


@pytest.mark.parametrize("arch", sorted(_TEMP_LIMIT))
def test_serve_step_updates_the_stacked_cache_in_place(one_chip, arch):
    from repro.models import api
    from repro.serving.decode import serve_step_jit
    cfg = get_config(arch)
    slots, positions = 32, 96 + 256 + 8
    params = _on_chip(jax.eval_shape(
        lambda: api.init_params(jax.random.PRNGKey(0), cfg)), one_chip)
    cache = _on_chip(jax.eval_shape(
        lambda: api.init_cache(cfg, slots, positions)), one_chip)
    token = _spec((slots, 1), jnp.int32, one_chip)
    compiled = serve_step_jit(cfg).lower(params, token, cache).compile()

    leaves = jax.tree.leaves(cache)
    # the state's arrays; a copy of the scalar length costs nothing
    shapes = {(_HLO_DTYPE[str(a.dtype)], tuple(a.shape))
              for a in leaves if a.ndim}
    assert not _entry_copies(compiled.as_text()) & shapes
    mem = compiled.memory_analysis()
    cache_bytes = sum(a.size * np.dtype(a.dtype).itemsize for a in leaves)
    assert mem.alias_size_in_bytes >= cache_bytes
    assert mem.temp_size_in_bytes < _TEMP_LIMIT[arch]
