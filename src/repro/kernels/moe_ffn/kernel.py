"""Pallas TPU kernel: fused per-expert SwiGLU FFN over dispatched tokens.

Operates on the capacity-dispatched layout (G, E, C, D) produced by the MoE
dispatch einsum. The fusion win vs. the three separate XLA einsums is that
the (C, F) gate/up intermediates never round-trip to HBM: for each f-tile we
compute silu(x@Wg_f) * (x@Wu_f) in VMEM and immediately accumulate its
down-projection into a (C, D) fp32 scratch accumulator. HBM traffic drops
from O(C*F) intermediates to just the x/weight tiles.

Grid: (G, E, C-tiles, F-tiles) with the F axis innermost/sequential.
Expert weights index via BlockSpec on the E coordinate — each core streams
only the tiles of the experts it owns (expert-parallel friendly).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _moe_ffn_kernel(
    x_ref,    # (1, 1, bc, D)
    wg_ref,   # (1, D, bf)
    wu_ref,   # (1, D, bf)
    wd_ref,   # (1, bf, D)
    o_ref,    # (1, 1, bc, D)
    acc_ref,  # scratch (bc, D) f32
):
    jf = pl.program_id(3)
    nf = pl.num_programs(3)

    @pl.when(jf == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[0, 0].astype(jnp.float32)    # (bc, D)
    wg = wg_ref[0].astype(jnp.float32)     # (D, bf)
    wu = wu_ref[0].astype(jnp.float32)
    wd = wd_ref[0].astype(jnp.float32)     # (bf, D)

    gate = jax.lax.dot_general(x, wg, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)
    up = jax.lax.dot_general(x, wu, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    h = jax.nn.silu(gate) * up             # (bc, bf) — stays in VMEM
    acc_ref[...] += jax.lax.dot_general(h, wd, (((1,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32)

    @pl.when(jf == nf - 1)
    def _done():
        o_ref[0, 0] = acc_ref[...].astype(o_ref.dtype)


def moe_expert_ffn(
    x: jax.Array,       # (G, E, C, D) dispatched tokens
    w_gate: jax.Array,  # (E, D, F)
    w_up: jax.Array,    # (E, D, F)
    w_down: jax.Array,  # (E, F, D)
    *,
    block_c: int = 128,
    block_f: int = 512,
    interpret: bool,
) -> jax.Array:
    g, e, c, d = x.shape
    f = w_gate.shape[-1]
    block_c = min(block_c, c)
    block_f = min(block_f, f)
    nc = pl.cdiv(c, block_c)
    nf = pl.cdiv(f, block_f)

    return pl.pallas_call(
        _moe_ffn_kernel,
        grid=(g, e, nc, nf),
        in_specs=[
            pl.BlockSpec((1, 1, block_c, d), lambda g, e, ic, jf: (g, e, ic, 0)),
            pl.BlockSpec((1, d, block_f), lambda g, e, ic, jf: (e, 0, jf)),
            pl.BlockSpec((1, d, block_f), lambda g, e, ic, jf: (e, 0, jf)),
            pl.BlockSpec((1, block_f, d), lambda g, e, ic, jf: (e, jf, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_c, d),
                               lambda g, e, ic, jf: (g, e, ic, 0)),
        out_shape=jax.ShapeDtypeStruct((g, e, c, d), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_c, d), jnp.float32)],
        interpret=interpret,
    )(x, w_gate, w_up, w_down)
