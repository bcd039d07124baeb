"""Reference Mamba-2 mixer (``granitemoehybrid``'s ``GraniteMoeHybridMamba
Layer``, one group): the normed input's projection split into z | xBC |
dt; a causal depthwise convolution of xBC over ``ssm_conv`` positions
with bias, then silu, split into x | B | C; ``dt = softplus(dt +
dt_bias)``, ``A = -exp(A_log)``; the recurrence run position by position,
``h = exp(dt A) h + dt x (x) B`` and ``y = C . h + D x``, per head; then
the gated norm ``rmsnorm(y * silu(z))`` over all of d_inner, and the
output projection.

Departures, each from the published module: the norm scales are in the
program's ``(1 + scale)`` form (``numerics.rmsnorm``); dt is not clamped
(the published ``time_step_limit`` is (0, inf), so it does not clamp
either); the recurrence is the per-position form, not the chunked scan
the program runs (the same state and outputs, summed in another order).
Float32, or the float8 control, which rounds the operands of the two
projections (``numerics.einsum``); the recurrence and the convolution are
elementwise and stay float32 in both.

Leaves: ``norm.scale`` (D), ``core.w_in`` (D, 2 di + 2 N + H),
``core.conv_w`` (K, di + 2 N), ``core.conv_b`` (di + 2 N), ``core.a_log``
/ ``core.d_skip`` / ``core.dt_bias`` (H), ``core.norm.scale`` (di),
``core.w_out`` (di, D); di = ``ssm_expand`` x D, H = di /
``ssm_head_dim``, N = ``ssm_state``."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from reference.numerics import einsum, rmsnorm

# the keys this module reads: see ``drivers/lm_serving.py`` ``check_config``
CHECKS = [("ssm_state", "ssm_state"), ("ssm_conv", "ssm_conv"),
          ("ssm_expand", "ssm_expand"), ("ssm_head_dim", "ssm_head_dim"),
          ("norm_eps", "norm_eps")]


def dims(model: dict) -> tuple[int, int, int, int]:
    """(d_inner, heads, state size, conv channels)."""
    di = model["ssm_expand"] * model["d_model"]
    N = model["ssm_state"]
    return di, di // model["ssm_head_dim"], N, di + 2 * N


def init_scale(leaf: str, shape: tuple[int, ...], model: dict) -> float:
    """Standard deviation of a leaf's random draw (0 for zeros): the
    projections at 1/sqrt(fan-in), the convolution at 1/sqrt(K) and its
    bias at PyTorch's default uniform's, A_log, dt_bias at 0.5 and D at 1
    (the published init sets D to ones and A_log, dt_bias to fixed ranges;
    a zero-mean draw keeps their scale)."""
    if leaf.endswith("scale"):
        return 0.0
    di, _, _, _ = dims(model)
    K = model["ssm_conv"]
    return {"core/w_in": 1.0 / math.sqrt(model["d_model"]),
            "core/w_out": 1.0 / math.sqrt(di),
            "core/conv_w": 1.0 / math.sqrt(K),
            "core/conv_b": 1.0 / math.sqrt(3 * K),
            "core/a_log": 0.5, "core/dt_bias": 0.5,
            "core/d_skip": 1.0}[leaf]


def apply(model: dict, p: dict, x, mode: str):
    """x: (S, D) float32 residual stream -> the sub-layer's output (S, D)."""
    S = x.shape[0]
    di, H, N, C = dims(model)
    P, K = model["ssm_head_dim"], model["ssm_conv"]
    c = p["core"]
    h = rmsnorm(p["norm"]["scale"], x, model["norm_eps"])
    zxbcdt = einsum("sd,df->sf", h, c["w_in"], mode)
    z, xbc, dt = zxbcdt[:, :di], zxbcdt[:, di:di + C], zxbcdt[:, di + C:]
    w = c["conv_w"].astype(jnp.float32)
    xp = jnp.concatenate([jnp.zeros((K - 1, C)), xbc])
    conv = sum(xp[i:i + S] * w[i] for i in range(K))
    conv = jax.nn.silu(conv + c["conv_b"].astype(jnp.float32))
    xs, b, cc = conv[:, :di], conv[:, di:di + N], conv[:, di + N:]
    dt = jax.nn.softplus(dt + c["dt_bias"].astype(jnp.float32))   # (S, H)
    a = -jnp.exp(c["a_log"].astype(jnp.float32))                  # (H,)
    xh = xs.reshape(S, H, P)

    def step(st, inp):
        x_t, dt_t, b_t, c_t = inp
        st = (jnp.exp(dt_t * a)[:, None, None] * st
              + (dt_t[:, None, None] * b_t[None, :, None])
              * x_t[:, None, :])                                   # (H,N,P)
        return st, jnp.sum(c_t[None, :, None] * st, axis=1)        # (H, P)

    _, y = jax.lax.scan(step, jnp.zeros((H, N, P)), (xh, dt, b, cc))
    y = y + xh * c["d_skip"].astype(jnp.float32)[:, None]
    g = y.reshape(S, di) * jax.nn.silu(z)
    y = rmsnorm(c["norm"]["scale"], g, model["norm_eps"])
    return einsum("sf,fd->sd", y, c["w_out"], mode)
