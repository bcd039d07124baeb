"""Reference mixture of experts: softmax router over the real experts, the
top-k picked, their gates renormalised to sum 1, every picked expert's
gated MLP applied to every token (dropless, as the published model routes).
Computed densely over all real experts with zero gates for the rest.
Leaves: ``norm.scale`` (D), ``moe.router`` (D, Ep), ``moe.w_gate`` /
``moe.w_up`` (Ep, D, F), ``moe.w_down`` (Ep, F, D); experts at and past
``moe_experts`` are padding that the program masks out of routing."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from reference.numerics import einsum, rmsnorm


def init_scale(leaf: str, shape: tuple[int, ...], model: dict) -> float:
    if leaf.endswith("scale"):
        return 0.0
    if leaf.endswith("w_down"):
        return 1.0 / math.sqrt(model["d_ff"])
    return 1.0 / math.sqrt(model["d_model"])


def padding(leaf: str, model: dict) -> tuple[int, int] | None:
    """(axis, first padded index) of a leaf whose tail is padding, which
    is drawn as zeros."""
    if leaf.endswith("router"):
        return 1, model["moe_experts"]
    if leaf.startswith("moe/"):
        return 0, model["moe_experts"]
    return None


def apply(model: dict, p: dict, x, mode: str):
    E, k = model["moe_experts"], model["moe_top_k"]
    h = rmsnorm(p["norm"]["scale"], x, model["norm_eps"])
    m = p["moe"]
    logits = einsum("sd,de->se", h, m["router"][:, :E], mode)
    probs = jax.nn.softmax(logits, axis=-1)
    top, idx = jax.lax.top_k(probs, k)
    gates = jnp.zeros_like(probs).at[
        jnp.arange(x.shape[0])[:, None], idx].set(top / jnp.sum(top, -1,
                                                               keepdims=True))
    g = jax.nn.silu(einsum("sd,edf->sef", h, m["w_gate"][:E], mode))
    u = einsum("sd,edf->sef", h, m["w_up"][:E], mode)
    y = einsum("sef,efd->sed", g * u, m["w_down"][:E], mode)
    return x + jnp.einsum("sed,se->sd", y, gates,
                          precision=jax.lax.Precision.HIGHEST)
