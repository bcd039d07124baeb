"""Reference causal self-attention with grouped K/V heads.  Positions are
rotary (rotate-half form, inverse frequencies theta^(-2i/head_dim)), or
none where the configuration says ``rope: false``.  Scores are scaled by
``attention_multiplier``, 1/sqrt(head_dim) where the configuration leaves
it out.  Leaves: ``norm.scale`` (D), ``attn.wq`` (D, H, hd), ``attn.wk`` /
``attn.wv`` (D, Hk, hd), ``attn.wo`` (H, hd, D)."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from reference.numerics import einsum, rmsnorm


def program_attention_multiplier(cfg) -> float:
    """The program's score scale: 1/sqrt(query_pre_attn_scalar), or
    1/sqrt(head_dim) where that is unset."""
    return getattr(cfg, "attention_multiplier", None) or 1.0 / math.sqrt(
        cfg.query_pre_attn_scalar or cfg.head_dim)


# the keys this module reads: see ``drivers/lm_serving.py`` ``check_config``
CHECKS = [("n_heads", "n_heads"), ("n_kv_heads", "n_kv_heads"),
          ("head_dim", "head_dim"), ("rope_theta", "rope_theta"),
          ("rope", "use_rope", True),
          ("attention_multiplier",
           ("query_pre_attn_scalar", program_attention_multiplier),
           lambda m: 1.0 / math.sqrt(m["head_dim"]))]


def init_scale(leaf: str, shape: tuple[int, ...], model: dict) -> float:
    """Standard deviation of a leaf's random draw (0 for zeros)."""
    if leaf.endswith("scale"):
        return 0.0
    if leaf.endswith("wo"):
        return 1.0 / math.sqrt(model["n_heads"] * model["head_dim"])
    return 1.0 / math.sqrt(model["d_model"])


def rope(x, pos, theta: float):
    """x: (S, heads, hd); pos: (S,)."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def apply(model: dict, p: dict, x, mode: str):
    """x: (S, D) float32 residual stream -> the sub-layer's output (S, D)."""
    S = x.shape[0]
    hd, H, Hk = model["head_dim"], model["n_heads"], model["n_kv_heads"]
    h = rmsnorm(p["norm"]["scale"], x, model["norm_eps"])
    a = p["attn"]
    q = einsum("sd,dhk->shk", h, a["wq"], mode)
    k = einsum("sd,dhk->shk", h, a["wk"], mode)
    v = einsum("sd,dhk->shk", h, a["wv"], mode)
    pos = jnp.arange(S)
    if model.get("rope", True):
        q = rope(q, pos, model["rope_theta"])
        k = rope(k, pos, model["rope_theta"])
    q = q.reshape(S, Hk, H // Hk, hd)
    s = einsum("sgrk,tgk->grst", q, k, mode)
    mult = model.get("attention_multiplier")
    # left out: the division that the 1/sqrt(head_dim) scale always was
    s = s / math.sqrt(hd) if mult is None else s * mult
    causal = pos[:, None] >= pos[None, :]
    s = jnp.where(causal, s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    o = einsum("grst,tgk->sgrk", w, v, mode).reshape(S, H, hd)
    return einsum("shk,hkd->sd", o, a["wo"], mode)
