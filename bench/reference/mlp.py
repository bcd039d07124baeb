"""Reference gated MLP (SwiGLU): ``silu(h W_gate) * (h W_up) W_down``.
Leaves: ``norm.scale`` (D), ``mlp.w_gate`` / ``mlp.w_up`` (D, F),
``mlp.w_down`` (F, D)."""

from __future__ import annotations

import math

import jax

from reference.numerics import einsum, rmsnorm

# the keys this module reads: see ``drivers/lm_serving.py`` ``check_config``
CHECKS = [("d_ff", "d_ff"), ("activation", "activation")]


def init_scale(leaf: str, shape: tuple[int, ...], model: dict) -> float:
    if leaf.endswith("scale"):
        return 0.0
    if leaf.endswith("w_down"):
        return 1.0 / math.sqrt(model["d_ff"])
    return 1.0 / math.sqrt(model["d_model"])


def apply(model: dict, p: dict, x, mode: str):
    """x: (S, D) float32 residual stream -> the sub-layer's output (S, D)."""
    h = rmsnorm(p["norm"]["scale"], x, model["norm_eps"])
    m = p["mlp"]
    g = jax.nn.silu(einsum("sd,df->sf", h, m["w_gate"], mode))
    u = einsum("sd,df->sf", h, m["w_up"], mode)
    return einsum("sf,fd->sd", g * u, m["w_down"], mode)
