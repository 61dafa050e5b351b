"""Mamba2 (SSD — state-space duality) blocks for mamba2-370m and zamba2.

The prefill path uses the chunked SSD algorithm from Dao & Gu (2024,
arXiv:2405.21060): within-chunk quadratic "attention" plus an inter-chunk
linear state recurrence — O(S * Q) compute, O(S) memory, and the chunk loop
is a ``lax.scan`` so HLO size is O(1) in sequence length.

The decode path is the O(1)-per-token recurrence over the (H, P, N) state
plus a width-4 causal conv ring buffer, which is what makes SSM/hybrid archs
the designated ``long_500k`` executors.

All SSD math runs in fp32; projections stay in the config compute dtype.
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.models import layers as L


class SSMState(NamedTuple):
    """Decode-time cache for one mamba block (stacked over layers by caller)."""
    ssm: jax.Array   # (B, H, P, N) fp32 state
    conv: jax.Array  # (B, W-1, conv_dim) last conv inputs


# --------------------------------------------------------------------------
# params
# --------------------------------------------------------------------------


def init_mamba(key, cfg: ModelConfig):
    dtype = L.dtype_of(cfg.param_dtype)
    d_in = cfg.ssm_d_inner
    nh = cfg.ssm_nheads
    k1, k2, k3, k4 = jax.random.split(key, 4)
    # A init in (1, 16) as in mamba2 reference
    a_init = jnp.exp(jax.random.uniform(k3, (nh,), jnp.float32,
                                        minval=jnp.log(1.0), maxval=jnp.log(16.0)))
    return {
        "in_proj": L.dense_init(k1, cfg.d_model, cfg.ssm_in_proj_dim, dtype),
        "conv_w": (jax.random.normal(k2, (cfg.ssm_conv_width, cfg.ssm_conv_dim),
                                     jnp.float32) * 0.1).astype(dtype),
        "conv_b": jnp.zeros((cfg.ssm_conv_dim,), dtype),
        "A_log": jnp.log(a_init),
        "D": jnp.ones((nh,), jnp.float32),
        "dt_bias": jnp.log(jnp.expm1(
            jnp.clip(jax.random.uniform(k4, (nh,), jnp.float32) * 0.1, 1e-3, 0.1))),
        "norm": L.init_rmsnorm(d_in),
        "out_proj": L.dense_init(jax.random.fold_in(key, 9), d_in, cfg.d_model, dtype),
    }


def _split_in_proj(cfg: ModelConfig, zxbcdt: jax.Array):
    d_in = cfg.ssm_d_inner
    gn = cfg.ssm_groups * cfg.ssm_state
    z, xbc, dt = jnp.split(zxbcdt, [d_in, d_in + d_in + 2 * gn], axis=-1)
    return z, xbc, dt


def _split_xbc(cfg: ModelConfig, xbc: jax.Array):
    d_in = cfg.ssm_d_inner
    gn = cfg.ssm_groups * cfg.ssm_state
    x, b, c = jnp.split(xbc, [d_in, d_in + gn], axis=-1)
    return x, b, c


# --------------------------------------------------------------------------
# chunked SSD (prefill / train)
# --------------------------------------------------------------------------


def ssd_chunked(
    x: jax.Array,    # (B, S, H, P) fp32
    dt: jax.Array,   # (B, S, H)    fp32 (already softplus'd)
    A: jax.Array,    # (H,)         fp32 (negative)
    Bm: jax.Array,   # (B, S, G, N) fp32
    Cm: jax.Array,   # (B, S, G, N) fp32
    D: jax.Array,    # (H,)
    chunk: int,
    initial_state: Optional[jax.Array] = None,  # (B, H, P, N)
) -> Tuple[jax.Array, jax.Array]:
    """Returns (y (B,S,H,P), final_state (B,H,P,N)). S % chunk must be 0."""
    b, s, h, p = x.shape
    g, n = Bm.shape[2], Bm.shape[3]
    hpg = h // g
    nc = s // chunk
    q = chunk

    def to_chunks(t):
        return t.reshape(b, nc, q, *t.shape[2:])

    xc, dtc = to_chunks(x), to_chunks(dt)
    bc, cc = to_chunks(Bm), to_chunks(Cm)

    a = dtc * A[None, None, None, :]                      # (B,nc,Q,H) log-decay
    a_cum = jnp.cumsum(a, axis=2)                          # inclusive cumsum

    # --- intra-chunk (quadratic within chunk) ---
    # L[i,j] = exp(a_cum[i] - a_cum[j]) for i >= j else 0
    seg = a_cum[:, :, :, None, :] - a_cum[:, :, None, :, :]   # (B,nc,Q,Q,H)
    causal = jnp.tril(jnp.ones((q, q), bool))
    lmat = jnp.where(causal[None, None, :, :, None], jnp.exp(seg), 0.0)
    # scores[i,j] = C_i . B_j (per group) -> (B,nc,H,Q,Q)
    scores = jnp.einsum("bcqgn,bckgn->bcgqk", cc, bc)
    scores = jnp.repeat(scores, hpg, axis=2)                  # expand groups->heads
    att = scores * jnp.transpose(lmat, (0, 1, 4, 2, 3))       # (B,nc,H,Q,Q)
    att = att * dtc.transpose(0, 1, 3, 2)[:, :, :, None, :]   # weight by dt_j
    y_intra = jnp.einsum("bchqk,bckhp->bcqhp", att, xc)

    # --- chunk states ---
    # state_c = sum_j exp(a_cum[last] - a_cum[j]) * dt_j * B_j x_j^T
    decay_to_end = jnp.exp(a_cum[:, :, -1:, :] - a_cum)       # (B,nc,Q,H)
    bx = jnp.einsum("bcqgn,bcqhp->bcqhpn",
                    bc, xc * (dtc * decay_to_end)[..., None])
    # heads in group share B: expand by repeating B over heads
    # (bx above already broadcasts g->h correctly only when g==1; general case:)
    if g != 1:
        bexp = jnp.repeat(bc, hpg, axis=3)                    # (B,nc,Q,H,N)
        bx = jnp.einsum("bcqhn,bcqhp->bcqhpn",
                        bexp, xc * (dtc * decay_to_end)[..., None])
    chunk_states = jnp.sum(bx, axis=2)                        # (B,nc,H,P,N)
    chunk_decay = jnp.exp(jnp.sum(a, axis=2))                 # (B,nc,H)

    # --- inter-chunk recurrence (scan over chunks) ---
    def step(h_prev, inp):
        st, dec = inp  # (B,H,P,N), (B,H)
        h_new = h_prev * dec[:, :, None, None] + st
        return h_new, h_prev

    h0 = (initial_state if initial_state is not None
          else jnp.zeros((b, h, p, n), jnp.float32))
    final_state, prev_states = jax.lax.scan(
        step,
        h0,
        (chunk_states.transpose(1, 0, 2, 3, 4), chunk_decay.transpose(1, 0, 2)),
    )
    prev_states = prev_states.transpose(1, 0, 2, 3, 4)        # (B,nc,H,P,N)

    # --- inter-chunk contribution: y_inter[i] = exp(a_cum[i]) * C_i . h_prev
    cexp = jnp.repeat(cc, hpg, axis=3) if g != 1 else None
    if g == 1:
        y_inter = jnp.einsum("bcqgn,bchpn->bcqhp", cc, prev_states)
    else:
        y_inter = jnp.einsum("bcqhn,bchpn->bcqhp", cexp, prev_states)
    y_inter = y_inter * jnp.exp(a_cum)[..., None]

    y = (y_intra + y_inter).reshape(b, s, h, p)
    y = y + x * D[None, None, :, None]
    return y, final_state


# --------------------------------------------------------------------------
# block-level prefill / decode
# --------------------------------------------------------------------------


def _causal_conv(xbc: jax.Array, w: jax.Array, bias: jax.Array,
                 prev: Optional[jax.Array] = None) -> jax.Array:
    """Depthwise causal conv1d. xbc: (B,S,C); w: (W,C). prev: (B,W-1,C)."""
    width = w.shape[0]
    if prev is None:
        prev = jnp.zeros((xbc.shape[0], width - 1, xbc.shape[2]), xbc.dtype)
    padded = jnp.concatenate([prev, xbc], axis=1)
    out = sum(padded[:, i:i + xbc.shape[1], :] * w[i][None, None, :]
              for i in range(width))
    return out + bias[None, None, :]


def mamba_prefill(
    params,
    cfg: ModelConfig,
    x: jax.Array,  # (B, S, D)
    initial: Optional[SSMState] = None,
) -> Tuple[jax.Array, SSMState]:
    b, s, _ = x.shape
    width = cfg.ssm_conv_width
    zxbcdt = jnp.einsum("bsd,de->bse", x, params["in_proj"])
    z, xbc_raw, dt = _split_in_proj(cfg, zxbcdt)
    conv_prev = initial.conv if initial is not None else None
    xbc = jax.nn.silu(_causal_conv(xbc_raw, params["conv_w"], params["conv_b"],
                                   conv_prev))
    xs, bm, cm = _split_xbc(cfg, xbc)

    nh, hd = cfg.ssm_nheads, cfg.ssm_head_dim
    xs = xs.reshape(b, s, nh, hd).astype(jnp.float32)
    bm = bm.reshape(b, s, cfg.ssm_groups, cfg.ssm_state).astype(jnp.float32)
    cm = cm.reshape(b, s, cfg.ssm_groups, cfg.ssm_state).astype(jnp.float32)
    dtv = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"][None, None, :])
    A = -jnp.exp(params["A_log"])

    chunk = min(cfg.ssm_chunk, s)
    if s % chunk != 0:  # pad sequence to a chunk multiple
        pad = chunk - s % chunk
        xs = jnp.pad(xs, ((0, 0), (0, pad), (0, 0), (0, 0)))
        bm = jnp.pad(bm, ((0, 0), (0, pad), (0, 0), (0, 0)))
        cm = jnp.pad(cm, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dtv = jnp.pad(dtv, ((0, 0), (0, pad), (0, 0)))

    init_state = initial.ssm if initial is not None else None
    if cfg.use_pallas:
        from repro.kernels.ssd_scan import ops as ssd_ops
        y, fstate = ssd_ops.ssd(xs, dtv, A, bm, cm, params["D"], chunk,
                                initial_state=init_state)
    else:
        y, fstate = ssd_chunked(xs, dtv, A, bm, cm, params["D"], chunk,
                                initial_state=init_state)
    y = y[:, :s].reshape(b, s, cfg.ssm_d_inner).astype(x.dtype)

    # gated rmsnorm then output projection
    y = L.rmsnorm(params["norm"], y * jax.nn.silu(z), cfg.norm_eps)
    out = jnp.einsum("bse,ed->bsd", y, params["out_proj"])
    prev = (initial.conv if initial is not None
            else jnp.zeros((b, width - 1, cfg.ssm_conv_dim), xbc_raw.dtype))
    conv_tail = jnp.concatenate([prev, xbc_raw], axis=1)[:, -(width - 1):, :]
    return out, SSMState(ssm=fstate, conv=conv_tail)


def init_ssm_state(cfg: ModelConfig, batch: int) -> SSMState:
    return SSMState(
        ssm=jnp.zeros((batch, cfg.ssm_nheads, cfg.ssm_head_dim, cfg.ssm_state),
                      jnp.float32),
        conv=jnp.zeros((batch, cfg.ssm_conv_width - 1, cfg.ssm_conv_dim),
                       L.dtype_of(cfg.dtype)),
    )


def mamba_decode(
    params,
    cfg: ModelConfig,
    x: jax.Array,  # (B, 1, D)
    state: SSMState,
) -> Tuple[jax.Array, SSMState]:
    b = x.shape[0]
    zxbcdt = jnp.einsum("bsd,de->bse", x, params["in_proj"])
    z, xbc_new, dt = _split_in_proj(cfg, zxbcdt)

    # conv ring buffer: append new input, convolve last W entries
    conv_in = jnp.concatenate([state.conv, xbc_new], axis=1)  # (B, W, C)
    xbc = jnp.einsum("bwc,wc->bc", conv_in, params["conv_w"]) + params["conv_b"]
    xbc = jax.nn.silu(xbc)[:, None, :]
    xs, bm, cm = _split_xbc(cfg, xbc)

    nh, hd = cfg.ssm_nheads, cfg.ssm_head_dim
    xs = xs.reshape(b, nh, hd).astype(jnp.float32)
    bm = bm.reshape(b, cfg.ssm_groups, cfg.ssm_state).astype(jnp.float32)
    cm = cm.reshape(b, cfg.ssm_groups, cfg.ssm_state).astype(jnp.float32)
    dtv = jax.nn.softplus(dt[:, 0].astype(jnp.float32) + params["dt_bias"][None, :])
    A = -jnp.exp(params["A_log"])

    hpg = nh // cfg.ssm_groups
    bexp = jnp.repeat(bm, hpg, axis=1)  # (B,H,N)
    cexp = jnp.repeat(cm, hpg, axis=1)
    decay = jnp.exp(dtv * A[None, :])  # (B,H)
    h_new = (state.ssm * decay[:, :, None, None]
             + jnp.einsum("bhn,bhp,bh->bhpn", bexp, xs, dtv))
    y = jnp.einsum("bhn,bhpn->bhp", cexp, h_new) + xs * params["D"][None, :, None]
    y = y.reshape(b, 1, cfg.ssm_d_inner).astype(x.dtype)

    y = L.rmsnorm(params["norm"], y * jax.nn.silu(z), cfg.norm_eps)
    out = jnp.einsum("bse,ed->bsd", y, params["out_proj"])
    return out, SSMState(ssm=h_new, conv=conv_in[:, 1:, :])
