"""Reference mixture of experts: a softmax router over all
``moe_router_experts`` published experts, the top-k picked, their gates
renormalised to sum 1, every picked expert's gated MLP applied to every
token (dropless, as the published model routes).  Of those experts this
chip holds the first ``moe_experts`` (all of them where the two agree):
only picked experts it holds contribute, and what the others would add is
left out, as a chip of an expert-parallel group computes its share.  A
shared expert of width ``moe_shared_d_ff`` (none at 0) is a gated MLP on
the same normed input, scaled by ``sigmoid(h shared_gate)`` where
``moe_shared_gate`` is "sigmoid" and not where it is null, and added to
the routed experts' output.  Computed densely over the held experts with
zero gates for the rest.

Leaves: ``norm.scale`` (D), ``moe.router`` (D, Rp), ``moe.w_gate`` /
``moe.w_up`` (Ep, D, F), ``moe.w_down`` (Ep, F, D), and with a shared
expert ``moe.shared.w_gate`` / ``moe.shared.w_up`` (D, Fs),
``moe.shared.w_down`` (Fs, D) and, gated, ``moe.shared_gate`` (D, 1).
Router columns at and past ``moe_router_experts`` and experts at and past
``moe_experts`` are padding that the program masks out of routing."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from reference.numerics import einsum, rmsnorm

# the keys this module reads: see ``drivers/lm_serving.py`` ``check_config``
CHECKS = [("d_ff", "d_ff"), ("activation", "activation"),
          ("moe_experts", "moe_experts"), ("moe_top_k", "moe_top_k"),
          ("moe_shared_d_ff", "moe_shared_dff", 0),
          ("moe_shared_gate",
           ("moe_shared_gate",
            lambda cfg: getattr(cfg, "moe_shared_gate", "sigmoid")),
           "sigmoid"),
          ("moe_router_experts",
           ("moe_router_experts",
            lambda cfg: getattr(cfg, "moe_router_experts", None)
            or cfg.moe_experts),
           lambda m: m["moe_experts"])]


def router_experts(model: dict) -> int:
    return model.get("moe_router_experts", model["moe_experts"])


def init_scale(leaf: str, shape: tuple[int, ...], model: dict) -> float:
    if leaf.endswith("scale"):
        return 0.0
    if leaf == "moe/shared/w_down":
        return 1.0 / math.sqrt(model["moe_shared_d_ff"])
    if leaf.endswith("w_down"):
        return 1.0 / math.sqrt(model["d_ff"])
    return 1.0 / math.sqrt(model["d_model"])


def padding(leaf: str, model: dict) -> tuple[int, int] | None:
    """(axis, first padded index) of a leaf whose tail is padding, which
    is drawn as zeros."""
    if leaf == "moe/router":
        return 1, router_experts(model)
    if leaf in ("moe/w_gate", "moe/w_up", "moe/w_down"):
        return 0, model["moe_experts"]
    return None


def routed(model: dict, m: dict, h, mode: str):
    """The held experts' share of the routed output, (S, D)."""
    E, R, k = model["moe_experts"], router_experts(model), model["moe_top_k"]
    logits = einsum("sd,de->se", h, m["router"][:, :R], mode)
    probs = jax.nn.softmax(logits, axis=-1)
    top, idx = jax.lax.top_k(probs, k)
    gates = jnp.zeros_like(probs).at[
        jnp.arange(h.shape[0])[:, None], idx].set(top / jnp.sum(top, -1,
                                                               keepdims=True))
    g = jax.nn.silu(einsum("sd,edf->sef", h, m["w_gate"][:E], mode))
    u = einsum("sd,edf->sef", h, m["w_up"][:E], mode)
    y = einsum("sef,efd->sed", g * u, m["w_down"][:E], mode)
    return jnp.einsum("sed,se->sd", y, gates[:, :E],
                      precision=jax.lax.Precision.HIGHEST)


def shared(model: dict, m: dict, h, mode: str):
    """The shared expert's output, (S, D); None without one."""
    if not model.get("moe_shared_d_ff", 0):
        return None
    s = m["shared"]
    g = jax.nn.silu(einsum("sd,df->sf", h, s["w_gate"], mode))
    u = einsum("sd,df->sf", h, s["w_up"], mode)
    y = einsum("sf,fd->sd", g * u, s["w_down"], mode)
    gate = model.get("moe_shared_gate", "sigmoid")
    if gate is None:
        return y
    if gate != "sigmoid":
        raise ValueError(f"unknown shared-expert gate {gate!r}")
    return jax.nn.sigmoid(einsum("sd,do->so", h, m["shared_gate"], mode)) * y


def apply(model: dict, p: dict, x, mode: str):
    """x: (S, D) float32 residual stream -> the sub-layer's output (S, D)."""
    h = rmsnorm(p["norm"]["scale"], x, model["norm_eps"])
    y = routed(model, p["moe"], h, mode)
    ys = shared(model, p["moe"], h, mode)
    return y if ys is None else y + ys
