"""Generic decoder-only LM over per-layer sub-layer patterns.

One class covers the dense, MoE, SSM, hybrid and VLM-backbone architectures:
the config's ``pattern`` lists each layer's sub-layer kinds, the whole stack
runs as a ``lax.scan`` over *groups* (one pattern repetition) with stacked
parameters — so the lowered HLO contains a single group body regardless of
depth, which keeps 512-way SPMD compiles tractable.  zamba2-style shared
blocks live outside the scanned stack and are applied once per group.

Execution modes:
* ``forward``      — full-sequence training path (remat per group),
* ``prefill``      — full sequence, builds decode caches,
* ``decode_step``  — one token against the caches (serve_step).
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from repro.runtime.sharding import constrain
from . import layers as L
from .layers import Pm
from .moe import moe, moe_spec
from .ssm import mamba2, mamba2_spec, mamba2_state_specs, mamba2_dims
from .xlstm import (mlstm, mlstm_spec, mlstm_state_specs, slstm, slstm_spec,
                    slstm_state_specs)


# ---------------------------------------------------------------------------
# Per-sub-layer specs and application
# ---------------------------------------------------------------------------

def _sub_spec(cfg, kind: str) -> dict:
    norm_spec, _ = L.make_norm(cfg.norm, cfg.d_model)
    if kind in ("attn", "attn_local"):
        s = {"norm": norm_spec,
             "attn": L.attention_spec(cfg.d_model, cfg.n_heads,
                                      cfg.n_kv_heads, cfg.head_dim,
                                      qkv_bias=cfg.qkv_bias)}
        if cfg.post_block_norm:
            s["post_norm"] = norm_spec
        return s
    if kind == "mlp":
        s = {"norm": norm_spec,
             "mlp": L.mlp_spec(cfg.d_model, cfg.d_ff, gated=cfg.mlp_gated)}
        if cfg.post_block_norm:
            s["post_norm"] = norm_spec
        return s
    if kind == "moe":
        return {"norm": norm_spec,
                "moe": moe_spec(cfg.d_model, cfg.d_ff, cfg.moe_experts,
                                n_shared=1 if cfg.moe_shared_dff else 0,
                                d_shared=cfg.moe_shared_dff)}
    if kind == "mamba2":
        return {"norm": norm_spec, "core": mamba2_spec(cfg)}
    if kind == "mlstm":
        return {"norm": norm_spec, "core": mlstm_spec(cfg)}
    if kind == "slstm":
        return {"core": slstm_spec(cfg)}
    raise ValueError(kind)


def _norm(cfg):
    if cfg.norm == "rmsnorm":
        return partial(L.rmsnorm, eps=cfg.norm_eps)
    return L.layernorm


def _residual(cfg, x, h):
    """The residual add, the sub-layer's output ``h`` times
    ``residual_multiplier`` (granite; nothing is emitted at 1.0)."""
    if cfg.residual_multiplier != 1.0:
        h = h * cfg.residual_multiplier
    return x + h


def apply_sublayer(cfg, kind, p, x, *, positions, cache, cache_len, mode,
                   lengths=None):
    """Returns (x, new_cache, aux).  ``lengths`` (prefill only): each
    row's real length; recurrent layers keep the positions at and past it
    out of their state (attention masks them by ``cache_len`` later)."""
    normf = _norm(cfg)
    aux = jnp.float32(0.0)

    if kind in ("attn", "attn_local"):
        h = normf(p["norm"], x)
        window = cfg.window if kind == "attn_local" else None
        h, new_cache = L.attention(
            p["attn"], h, positions=positions, rope_theta=cfg.rope_theta,
            causal=True, window=window, softcap=cfg.attn_softcap,
            kv_cache=cache, cache_len=cache_len, use_rope=cfg.use_rope,
            q_chunk=cfg.q_chunk,
            query_pre_attn_scalar=cfg.query_pre_attn_scalar)
        if cfg.post_block_norm:
            h = normf(p["post_norm"], h)
        return _residual(cfg, x, h), new_cache, aux

    if kind == "mlp":
        h = normf(p["norm"], x)
        h = L.mlp(p["mlp"], h, activation=cfg.activation)
        if cfg.post_block_norm:
            h = normf(p["post_norm"], h)
        return _residual(cfg, x, h), None, aux

    if kind == "moe":
        h = normf(p["norm"], x)
        h, aux = moe(p["moe"], h, top_k=cfg.moe_top_k,
                     n_experts=cfg.moe_experts,
                     capacity_factor=cfg.moe_capacity_factor,
                     activation=cfg.activation,
                     group_size=cfg.moe_group_size,
                     impl=cfg.moe_impl)
        return _residual(cfg, x, h), None, aux

    if kind == "mamba2":
        h = normf(p["norm"], x)
        st = cache or {}
        h, (ssm_st, conv_st) = mamba2(p["core"], cfg, h,
                                      state=st.get("ssm"),
                                      conv_state=st.get("conv"),
                                      decode=(mode == "decode"),
                                      lengths=lengths)
        new_cache = None if mode == "train" else \
            {"ssm": ssm_st, "conv": conv_st}
        return _residual(cfg, x, h), new_cache, aux

    if kind == "mlstm":
        h = normf(p["norm"], x)
        st = cache or {}
        h, (mat, conv_st) = mlstm(p["core"], cfg, h, state=st.get("mat"),
                                  conv_state=st.get("conv"),
                                  decode=(mode == "decode"))
        new_cache = None if mode == "train" else \
            {"mat": mat, "conv": conv_st}
        return _residual(cfg, x, h), new_cache, aux

    if kind == "slstm":
        st = cache.get("s") if cache else None
        x, new_st = slstm(p["core"], cfg, x, state=st,
                          decode=(mode == "decode"))
        new_cache = None if mode == "train" else {"s": new_st}
        return x, new_cache, aux

    raise ValueError(kind)


def _sub_cache_spec(cfg, kind: str, batch: int, max_len: int):
    """(ShapeDtypeStruct, logical-axes) pytree for one sub-layer's cache."""
    if kind in ("attn", "attn_local"):
        sds, axes = L.attention_cache_spec(cfg, batch, max_len)
        return {"k": (sds, axes), "v": (sds, axes)}
    if kind == "mamba2":
        (s, sa), (c, ca) = mamba2_state_specs(cfg, batch)
        return {"ssm": (s, sa), "conv": (c, ca)}
    if kind == "mlstm":
        (m, ma), (c, ca) = mlstm_state_specs(cfg, batch)
        return {"mat": (m, ma), "conv": (c, ca)}
    if kind == "slstm":
        return {"s": tuple(slstm_state_specs(cfg, batch))}
    return None


# ---------------------------------------------------------------------------
# The model
# ---------------------------------------------------------------------------

class DecoderLM:
    def __init__(self, cfg):
        self.cfg = cfg
        self.kinds = list(cfg.group_kinds)
        self.sub_names = [f"s{i}_{k}" for i, k in enumerate(self.kinds)]

    # -- parameter trees ----------------------------------------------------
    def group_spec(self) -> dict:
        return {n: _sub_spec(self.cfg, k)
                for n, k in zip(self.sub_names, self.kinds)}

    def spec(self) -> dict:
        cfg = self.cfg
        norm_spec, _ = L.make_norm(cfg.norm, cfg.d_model)
        spec = {
            "embed": L.embed_spec(cfg.vocab, cfg.d_model),
            "final_norm": norm_spec,
            "layers": L.stack_spec(self.group_spec(), cfg.n_groups),
        }
        if cfg.shared_attn_period:
            spec["shared_block"] = {
                "norm1": norm_spec,
                "attn": L.attention_spec(cfg.d_model, cfg.n_heads,
                                         cfg.n_kv_heads, cfg.head_dim),
                "norm2": norm_spec,
                "mlp": L.mlp_spec(cfg.d_model, cfg.d_ff, gated=cfg.mlp_gated),
            }
        return spec

    def init(self, key, dtype=jnp.bfloat16):
        return L.init_tree(self.spec(), key, dtype)

    def abstract_params(self, dtype=jnp.bfloat16):
        return L.abstract_tree(self.spec(), dtype)

    def param_axes(self):
        return L.axes_tree(self.spec())

    # -- caches ---------------------------------------------------------------
    def cache_spec(self, batch: int, max_len: int) -> dict:
        """Stacked (G, ...) decode-cache specs: {(name): {leaf: (sds, axes)}}."""
        cfg = self.cfg
        out = {}
        for n, k in zip(self.sub_names, self.kinds):
            sub = _sub_cache_spec(cfg, k, batch, max_len)
            if sub is None:
                continue
            out[n] = jax.tree.map(
                lambda t: (jax.ShapeDtypeStruct((cfg.n_groups, *t[0].shape),
                                                t[0].dtype),
                           ("layers", *t[1])),
                sub, is_leaf=lambda t: isinstance(t, tuple) and len(t) == 2
                and hasattr(t[0], "shape"))
        if cfg.shared_attn_period:
            sds, axes = L.attention_cache_spec(cfg, batch, max_len)
            out["shared_attn"] = {
                "k": (jax.ShapeDtypeStruct((cfg.n_groups, *sds.shape),
                                           sds.dtype), ("layers", *axes)),
                "v": (jax.ShapeDtypeStruct((cfg.n_groups, *sds.shape),
                                           sds.dtype), ("layers", *axes)),
            }
        return out

    def init_cache(self, batch: int, max_len: int):
        spec = self.cache_spec(batch, max_len)
        return jax.tree.map(
            lambda t: jnp.zeros(t[0].shape, t[0].dtype), spec,
            is_leaf=lambda t: isinstance(t, tuple) and len(t) == 2)

    # -- stack ---------------------------------------------------------------
    def _apply_group(self, params_g, shared, x, cache_g, *, positions,
                     cache_len, mode, lengths=None):
        aux = jnp.float32(0.0)
        new_cache = {}
        for n, k in zip(self.sub_names, self.kinds):
            c = cache_g.get(n) if cache_g else None
            x, nc, a = apply_sublayer(self.cfg, k, params_g[n], x,
                                      positions=positions, cache=c,
                                      cache_len=cache_len, mode=mode,
                                      lengths=lengths)
            aux = aux + a
            if nc is not None:
                new_cache[n] = nc
        if shared is not None:
            normf = _norm(self.cfg)
            h = normf(shared["norm1"], x)
            c = cache_g.get("shared_attn") if cache_g else None
            h, nc = L.attention(shared["attn"], h, positions=positions,
                                rope_theta=self.cfg.rope_theta, causal=True,
                                kv_cache=c, cache_len=cache_len,
                                q_chunk=self.cfg.q_chunk)
            x = _residual(self.cfg, x, h)
            h = normf(shared["norm2"], x)
            x = _residual(self.cfg, x, L.mlp(shared["mlp"], h,
                                             activation=self.cfg.activation))
            if nc is not None:
                new_cache["shared_attn"] = nc
        return x, new_cache, aux

    def _stack(self, params, x, caches, *, positions, cache_len, mode,
               lengths=None):
        cfg = self.cfg
        shared = params.get("shared_block")

        def body_fn(x, params_g, cache_g):
            return self._apply_group(params_g, shared, x, cache_g,
                                     positions=positions,
                                     cache_len=cache_len, mode=mode,
                                     lengths=lengths)

        if cfg.remat and mode == "train":
            body_fn = jax.checkpoint(body_fn)

        if not cfg.scan_layers:
            # unrolled stack — used by the dry-run costing variants (XLA
            # cost analysis counts a while body once, so scanned layers are
            # invisible to it; an unrolled 2-vs-3-group pair recovers the
            # true per-group cost slope)
            aux = jnp.float32(0.0)
            new_caches = caches
            for gi in range(cfg.n_groups):
                pg = jax.tree.map(lambda a: a[gi], params["layers"])
                cg = (None if caches is None else
                      jax.tree.map(lambda c: c[gi], new_caches))
                x, ncg, a = body_fn(x, pg, cg)
                aux = aux + a
                if caches is not None:
                    new_caches = jax.tree.map(
                        lambda c, nv: c.at[gi].set(nv.astype(c.dtype)),
                        new_caches, ncg)
            return x, new_caches, aux

        if caches is None:
            def body(carry, pg):
                x, aux = carry
                x, _, a = body_fn(x, pg, None)
                return (x, aux + a), None

            (x, aux), _ = jax.lax.scan(body, (x, jnp.float32(0.0)),
                                       params["layers"])
            return x, None, aux

        # Caches ride in the CARRY (updated in place with dynamic slices),
        # not as scan xs/ys — xs+ys would hold the old and new cache
        # simultaneously, doubling decode HBM (observed +7 GiB on
        # gemma-7b decode_32k; EXPERIMENTS.md §Perf).
        def body(carry, xs):
            x, aux, caches = carry
            pg, g = xs
            cg = jax.tree.map(
                lambda c: jax.lax.dynamic_index_in_dim(c, g, 0,
                                                       keepdims=False),
                caches)
            x, ncg, a = body_fn(x, pg, cg)
            caches = jax.tree.map(
                lambda c, n: jax.lax.dynamic_update_index_in_dim(
                    c, n.astype(c.dtype), g, 0), caches, ncg)
            return (x, aux + a, caches), None

        (x, aux, new_caches), _ = jax.lax.scan(
            body, (x, jnp.float32(0.0), caches),
            (params["layers"], jnp.arange(cfg.n_groups, dtype=jnp.int32)))
        return x, new_caches, aux

    # -- entry points ---------------------------------------------------------
    def _embed_inputs(self, params, tokens, patch_embeds=None):
        x = L.embed(params["embed"], tokens,
                    scale_by_dim=self.cfg.embed_scale)
        if self.cfg.embedding_multiplier != 1.0:
            x = x * self.cfg.embedding_multiplier
        if patch_embeds is not None:
            x = jnp.concatenate([patch_embeds.astype(x.dtype), x], axis=1)
        return constrain(x, "act_batch", "act_seq", None)

    def forward(self, params, tokens, patch_embeds=None):
        """Training/eval full-sequence pass -> final hidden states."""
        x = self._embed_inputs(params, tokens, patch_embeds)
        S = x.shape[1]
        positions = jnp.arange(S, dtype=jnp.int32)[None, :]
        x, _, aux = self._stack(params, x, None, positions=positions,
                                cache_len=None, mode="train")
        normf = _norm(self.cfg)
        return normf(params["final_norm"], x), aux

    def loss(self, params, batch):
        """batch: {"tokens": (B,S), "labels": (B,S) [, "patch_embeds"]}"""
        hidden, aux = self.forward(params, batch["tokens"],
                                   batch.get("patch_embeds"))
        labels = batch["labels"]
        n_img = self.cfg.n_img_tokens if "patch_embeds" in batch else 0
        if n_img:
            # keep the full (sharded) sequence; image positions carry
            # label -1 and are masked inside the loss — slicing the
            # seq-sharded hidden would force a reshard of every cotangent
            pad = jnp.full((labels.shape[0], n_img), -1, labels.dtype)
            labels = jnp.concatenate([pad, labels], axis=1)
        nll = L.cross_entropy_loss(params["embed"], hidden, labels,
                                   softcap=self.cfg.final_softcap,
                                   seq_chunk=self.cfg.loss_seq_chunk,
                                   scaling=self.cfg.logits_scaling)
        return nll + 0.01 * aux, {"nll": nll, "aux": aux}

    def prefill(self, params, tokens, cache, patch_embeds=None,
                lengths=None):
        """Fill caches with the prompt; returns (last_logits, caches).

        ``lengths`` (B,): each row's real length in a right-padded batch;
        recurrent layers then stop their state at it.  The logits stay
        those of the last position."""
        x = self._embed_inputs(params, tokens, patch_embeds)
        S = x.shape[1]
        positions = jnp.arange(S, dtype=jnp.int32)[None, :]
        x, caches, _ = self._stack(params, x, cache, positions=positions,
                                   cache_len=jnp.int32(0), mode="prefill",
                                   lengths=lengths)
        normf = _norm(self.cfg)
        hidden = normf(params["final_norm"], x[:, -1:])
        return self._logits(params, hidden), caches

    def _logits(self, params, hidden):
        return L.unembed(params["embed"], hidden,
                         softcap=self.cfg.final_softcap,
                         scaling=self.cfg.logits_scaling)

    def decode_step(self, params, token, cache, cache_len):
        """token: (B, 1) int32; cache_len: filled length — scalar for
        uniform decode (fleet cells) or (B,) for ragged serving batches."""
        x = self._embed_inputs(params, token)
        clen = jnp.asarray(cache_len)
        if clen.ndim == 1:
            positions = clen[:, None] + jnp.arange(1, dtype=jnp.int32)[None]
        else:
            positions = (clen + jnp.arange(1, dtype=jnp.int32))[None, :]
        x, caches, _ = self._stack(params, x, cache, positions=positions,
                                   cache_len=cache_len, mode="decode")
        normf = _norm(self.cfg)
        hidden = normf(params["final_norm"], x)
        return self._logits(params, hidden), caches
