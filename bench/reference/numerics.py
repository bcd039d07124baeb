"""Arithmetic of the plain reference: float32 at "highest" matmul precision,
or the control, which rounds both operands of every matmul to float8 e4m3
with one scale per tensor (the next precision below the bfloat16 that the
configurations serve in)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

E4M3_MAX = 448.0


def fp8(x):
    """Round ``x`` to float8 e4m3 with one per-tensor scale, back to f32."""
    x = x.astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / E4M3_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def einsum(spec: str, a, b, mode: str):
    """``jnp.einsum`` in the reference's arithmetic: ``mode`` is "f32" or
    "fp8" (operands rounded to e4m3, products summed in f32)."""
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    if mode == "fp8":
        a, b = fp8(a), fp8(b)
    elif mode != "f32":
        raise ValueError(f"unknown reference arithmetic {mode!r}")
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)


def rmsnorm(scale, x, eps: float):
    """RMSNorm in the ``(1 + scale)`` form."""
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + scale.astype(jnp.float32))
