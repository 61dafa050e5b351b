"""The Mamba2 layer as the benchmark's plain reference sees it.

Used by the ``ssm`` family (mamba2) and by ``oracle.py`` (the control's
arithmetic). Three things live here, all computed from the configuration
file's sizes
and nothing of the program under test:

- ``init_layer``: the layer's weights from a PRNG key, drawn the way the
  served program seeds its own (same key splits, same distributions, the
  same bf16 rounding), so that the reference and the program hold equal
  weights without either handing the other an array;
- ``mixer``, and ``layer`` (pre-norm, mixer, unit residual): the forward
  pass over a whole sequence in plain ``jax.numpy``: the SSM as its
  defining recurrence, one step per position, never the chunked (SSD)
  form the program runs;
- the operation and byte counts of one token through the layer, and of
  the state one decode slot keeps.

Equations (Dao & Gu 2024, arXiv:2405.21060), per position t:
``h_t = exp(dt_t * A) * h_{t-1} + dt_t * x_t B_t^T`` and
``y_t = h_t C_t + D * x_t``, after a width-4 causal depthwise convolution
and SiLU over (x, B, C), with ``dt = softplus(dt_raw + dt_bias)``,
``A = -exp(A_log)``, and a gated RMS norm ``norm(y * silu(z))`` before
the output projection. RMS norms scale by ``(1 + w)``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

BF16 = jnp.bfloat16
F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST


def dims(c):
    """(d_inner, heads, groups x state, conv channels, in_proj width)."""
    d_in = c["expand"] * c["hidden_size"]
    nh = d_in // c["head_dim"]
    gn = c["n_groups"] * c["state_size"]
    conv_dim = d_in + 2 * gn
    return d_in, nh, gn, conv_dim, 2 * d_in + 2 * gn + nh


# --------------------------------------------------------------------------
# weights
# --------------------------------------------------------------------------


def dense(key, fan_in: int, fan_out: int):
    """Truncated normal (+-2 sigma) with std 1/sqrt(fan_in), rounded to
    bf16: the served program's projection initializer."""
    std = 1.0 / np.sqrt(fan_in)
    return (jax.random.truncated_normal(key, -2.0, 2.0, (fan_in, fan_out),
                                        F32) * std).astype(BF16)


def init_layer(key, c):
    d_model, width = c["hidden_size"], c["conv_kernel"]
    d_in, nh, _, conv_dim, in_proj = dims(c)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    a_init = jnp.exp(jax.random.uniform(k3, (nh,), F32,
                                        minval=jnp.log(1.0),
                                        maxval=jnp.log(16.0)))
    return {
        "pre_norm": jnp.zeros((d_model,), F32),
        "in_proj": dense(k1, d_model, in_proj),
        "conv_w": (jax.random.normal(k2, (width, conv_dim), F32)
                   * 0.1).astype(BF16),
        "conv_b": jnp.zeros((conv_dim,), BF16),
        "A_log": jnp.log(a_init),
        "D": jnp.ones((nh,), F32),
        "dt_bias": jnp.log(jnp.expm1(jnp.clip(
            jax.random.uniform(k4, (nh,), F32) * 0.1, 1e-3, 0.1))),
        "gate_norm": jnp.zeros((d_in,), F32),
        "out_proj": dense(jax.random.fold_in(key, 9), d_in, d_model),
    }


def init_stack(key, c, n: int):
    """``n`` layers stacked on a leading axis, one key each."""
    return jax.vmap(lambda k: init_layer(k, c))(jax.random.split(key, n))


def embed_table(key, c):
    return (jax.random.normal(key, (c["vocab_size"], c["hidden_size"]), F32)
            * 0.02).astype(BF16)


# --------------------------------------------------------------------------
# arithmetic: float32 at "highest", or the fp8 control
# --------------------------------------------------------------------------


def _fp8(a, axis):
    """Round to float8_e4m3fn with one absmax scale per slice along
    ``axis`` (the contracting axis), and back to float32."""
    scale = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / 448.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return (a / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


class Arith:
    """Matrix products of the reference. ``"f32"``: float32 operands at
    HIGHEST precision. ``"fp8"``: the control, the same products with
    both operands rounded to fp8 (e4m3, scaled per slice along the
    contracting axis), accumulated in float32; everything else stays
    float32."""

    def __init__(self, mode: str):
        if mode not in ("f32", "fp8"):
            raise ValueError(f"unknown arithmetic {mode!r}")
        self.mode = mode

    def mm(self, eq: str, a, b, a_axis: int, b_axis: int):
        a, b = a.astype(F32), b.astype(F32)
        if self.mode == "fp8":
            a, b = _fp8(a, a_axis), _fp8(b, b_axis)
        return jnp.einsum(eq, a, b, precision=HIGHEST)


def rmsnorm(x, w, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + w)


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------


def layer(p, x, c, ar: Arith):
    """One Mamba2 layer with its residual: x (B, S, D) float32."""
    return x + mixer(p, rmsnorm(x, p["pre_norm"], c["norm_eps"]), c, ar)


def mixer(p, h, c, ar: Arith):
    """The Mamba2 mixer from its normed input h (B, S, D) float32 to the
    output projection, without the residual: a family whose layer scales
    the branch or follows it with an FFN builds its layer round this."""
    b, s, _ = h.shape
    d_in, nh, gn, conv_dim, _ = dims(c)
    hd, n, g = c["head_dim"], c["state_size"], c["n_groups"]
    eps = c["norm_eps"]
    zxbcdt = ar.mm("bsd,de->bse", h, p["in_proj"], -1, 0)
    z = zxbcdt[..., :d_in]
    xbc = zxbcdt[..., d_in:d_in + conv_dim]
    dt = zxbcdt[..., d_in + conv_dim:]

    w = p["conv_w"].astype(F32)
    width = w.shape[0]
    padded = jnp.concatenate(
        [jnp.zeros((b, width - 1, conv_dim), F32), xbc], axis=1)
    conv = sum(padded[:, i:i + s] * w[i] for i in range(width))
    xbc = jax.nn.silu(conv + p["conv_b"].astype(F32))
    xs = xbc[..., :d_in].reshape(b, s, nh, hd)
    bm = jnp.repeat(xbc[..., d_in:d_in + gn].reshape(b, s, g, n),
                    nh // g, axis=2)
    cm = jnp.repeat(xbc[..., d_in + gn:].reshape(b, s, g, n),
                    nh // g, axis=2)
    dt = jax.nn.softplus(dt + p["dt_bias"])
    a = -jnp.exp(p["A_log"])

    def step(state, inp):
        x_t, dt_t, b_t, c_t = inp    # (B,H,P) (B,H) (B,H,N) (B,H,N)
        state = (state * jnp.exp(dt_t * a)[:, :, None, None]
                 + (dt_t[:, :, None] * x_t)[..., None] * b_t[:, :, None, :])
        return state, jnp.einsum("bhpn,bhn->bhp", state, c_t,
                                 precision=HIGHEST)

    time_major = (xs.transpose(1, 0, 2, 3), dt.transpose(1, 0, 2),
                  bm.transpose(1, 0, 2, 3), cm.transpose(1, 0, 2, 3))
    _, ys = jax.lax.scan(step, jnp.zeros((b, nh, hd, n), F32), time_major)
    y = ys.transpose(1, 0, 2, 3) + xs * p["D"][:, None]
    y = rmsnorm(y.reshape(b, s, d_in) * jax.nn.silu(z), p["gate_norm"], eps)
    return ar.mm("bse,ed->bsd", y, p["out_proj"], -1, 0)


def unembed(table, x, ar: Arith):
    return ar.mm("bsd,vd->bsv", x, table, -1, -1)


# --------------------------------------------------------------------------
# counts
# --------------------------------------------------------------------------


def layer_param_bytes(c) -> int:
    """Bytes of one layer's weights as served: projections and the conv
    in bf16, norms and the per-head scalars in float32."""
    d_model, width = c["hidden_size"], c["conv_kernel"]
    d_in, nh, _, conv_dim, in_proj = dims(c)
    bf16 = 2 * (d_model * in_proj + width * conv_dim + conv_dim
                + d_in * d_model)
    f32 = 4 * (d_model + 3 * nh + d_in)
    return bf16 + f32


def token_flops(c) -> int:
    """Operations one token needs through one layer: the two projections
    and the convolution (2 per multiply-add), the state update (decay,
    outer product and add: 3 per state element) and the read-out (2 per
    state element). Norms and activations are not counted."""
    d_model, width = c["hidden_size"], c["conv_kernel"]
    d_in, nh, _, conv_dim, in_proj = dims(c)
    state = nh * c["head_dim"] * c["state_size"]
    return (2 * d_model * in_proj + 2 * d_in * d_model
            + 2 * width * conv_dim + 5 * state)


def slot_state_bytes(c) -> int:
    """Bytes one decode slot keeps per layer: the SSM state at the
    configuration's state dtype and the conv tail at its compute dtype."""
    _, nh, _, conv_dim, _ = dims(c)
    ssm = nh * c["head_dim"] * c["state_size"] * _itemsize(
        c["ssm_state_dtype"])
    conv = (c["conv_kernel"] - 1) * conv_dim * _itemsize(
        c["conv_state_dtype"])
    return ssm + conv


def _itemsize(name: str) -> int:
    return {"float32": 4, "bfloat16": 2}[name]
