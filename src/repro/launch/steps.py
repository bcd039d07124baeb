"""Step functions (train / prefill / decode) + input specs + shardings.

Everything here is mesh-agnostic: the dry-run, the trainer and the serving
engine all build their jitted programs from these factories.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeConfig
from repro.models import build_model
from repro.optim import AdamWConfig, apply_updates, init_state
from repro.runtime.sharding import (AxisRules, _divisible_spec, use_rules)


# ---------------------------------------------------------------------------
# step factories
# ---------------------------------------------------------------------------

def make_train_step(model, adamw_cfg: AdamWConfig, rules: AxisRules | None,
                    mesh: Mesh | None):
    def train_step(params, opt_state, batch):
        with use_rules(rules, mesh):
            (loss, metrics), grads = jax.value_and_grad(
                model.loss, has_aux=True)(params, batch)
            params, opt_state, om = apply_updates(adamw_cfg, params, grads,
                                                  opt_state)
            return params, opt_state, {"loss": loss, **metrics, **om}

    return train_step


def make_prefill_step(model, rules: AxisRules | None, mesh: Mesh | None):
    def prefill_step(params, cache, batch):
        with use_rules(rules, mesh):
            kw = {}
            if "frames" in batch:
                kw["frames"] = batch["frames"]
            if "patch_embeds" in batch:
                kw["patch_embeds"] = batch["patch_embeds"]
            logits, cache = model.prefill(params, batch["tokens"], cache,
                                          **kw)
            return logits, cache

    return prefill_step


def make_fresh_prefill_step(model, max_len: int):
    """``prefill_step(params, batch)``: :func:`make_prefill_step` into a
    zeros cache of the batch's rows and ``max_len`` positions, built inside
    the same program.  The caller holds no scratch cache, and the zeroing
    is part of the prefill's device time."""
    step = make_prefill_step(model, None, None)

    def prefill_step(params, batch):
        cache = model.init_cache(batch=batch["tokens"].shape[0],
                                 max_len=max_len)
        return step(params, cache, batch)

    return prefill_step


def make_decode_step(model, rules: AxisRules | None, mesh: Mesh | None):
    def decode_step(params, cache, token, cache_len):
        with use_rules(rules, mesh):
            logits, cache = model.decode_step(params, token, cache,
                                              cache_len)
            # greedy next token: what the serving engine feeds back
            next_tok = jnp.argmax(logits[:, -1], axis=-1
                                  ).astype(jnp.int32)[:, None]
            return next_tok, logits, cache

    return decode_step


# ---------------------------------------------------------------------------
# input specs (ShapeDtypeStructs — no allocation)
# ---------------------------------------------------------------------------

def batch_specs(cfg: ModelConfig, shape: ShapeConfig) -> dict[str, Any]:
    B, S = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    if cfg.is_encdec:
        return {
            "tokens": jax.ShapeDtypeStruct((B, S), i32),
            "labels": jax.ShapeDtypeStruct((B, S), i32),
            "frames": jax.ShapeDtypeStruct((B, cfg.encoder_len, cfg.d_model),
                                           jnp.bfloat16),
        }
    if cfg.n_img_tokens:
        S_text = S - cfg.n_img_tokens
        return {
            "tokens": jax.ShapeDtypeStruct((B, S_text), i32),
            "labels": jax.ShapeDtypeStruct((B, S_text), i32),
            "patch_embeds": jax.ShapeDtypeStruct(
                (B, cfg.n_img_tokens, cfg.d_model), jnp.bfloat16),
        }
    return {"tokens": jax.ShapeDtypeStruct((B, S), i32),
            "labels": jax.ShapeDtypeStruct((B, S), i32)}


def input_specs(cfg: ModelConfig, shape: ShapeConfig,
                dtype=jnp.bfloat16) -> dict[str, Any]:
    """All abstract inputs for the given cell.  Keys depend on the kind:

    train   -> params, opt_state, batch
    prefill -> params, cache, batch (labels dropped)
    decode  -> params, cache, token, cache_len
    """
    model = build_model(cfg)
    params = model.abstract_params(dtype)
    if shape.kind == "train":
        mu = jax.tree.map(
            lambda p: jax.ShapeDtypeStruct(p.shape, jnp.float32), params)
        opt = {"mu": mu, "nu": mu, "step": jax.ShapeDtypeStruct((), jnp.int32)}
        return {"params": params, "opt_state": opt,
                "batch": batch_specs(cfg, shape)}

    cache = jax.tree.map(
        lambda t: t[0], model.cache_spec(shape.global_batch, shape.seq_len),
        is_leaf=_is_spec_leaf)
    if shape.kind == "prefill":
        batch = batch_specs(cfg, shape)
        batch.pop("labels")
        return {"params": params, "cache": cache, "batch": batch}

    # decode
    return {"params": params, "cache": cache,
            "token": jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32),
            "cache_len": jax.ShapeDtypeStruct((), jnp.int32)}


def _is_spec_leaf(t):
    return (isinstance(t, tuple) and len(t) == 2
            and hasattr(t[0], "shape") and isinstance(t[1], tuple))


# ---------------------------------------------------------------------------
# shardings
# ---------------------------------------------------------------------------

def _shard(mesh: Mesh, rules: AxisRules, axes: tuple, shape: tuple
           ) -> NamedSharding:
    spec = _divisible_spec(mesh, rules.spec(axes), shape)
    return NamedSharding(mesh, spec)


def shardings_for(cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh,
                  rules: AxisRules, specs: dict[str, Any]) -> dict[str, Any]:
    """NamedSharding pytrees matching :func:`input_specs` output."""
    model = build_model(cfg)
    paxes = model.param_axes()
    pshard = jax.tree.map(
        lambda sds, axes: _shard(mesh, rules, axes, sds.shape),
        specs["params"], paxes,
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
    out: dict[str, Any] = {"params": pshard}

    if shape.kind == "train":
        out["opt_state"] = {
            "mu": pshard, "nu": pshard,
            "step": NamedSharding(mesh, P())}
        out["batch"] = {
            k: _shard(mesh, rules, ("act_batch", None, None)[:v.ndim],
                      v.shape)
            for k, v in specs["batch"].items()}
        return out

    cspec = model.cache_spec(shape.global_batch, shape.seq_len)
    out["cache"] = jax.tree.map(
        lambda t: _shard(mesh, rules, t[1], t[0].shape), cspec,
        is_leaf=_is_spec_leaf)
    if shape.kind == "prefill":
        out["batch"] = {
            k: _shard(mesh, rules, ("act_batch", None, None)[:v.ndim],
                      v.shape)
            for k, v in specs["batch"].items()}
    else:
        out["token"] = _shard(mesh, rules, ("act_batch", None),
                              specs["token"].shape)
        out["cache_len"] = NamedSharding(mesh, P())
    return out


def rules_for(shape: ShapeConfig, *, multi_pod: bool) -> AxisRules:
    from repro.runtime.sharding import multi_pod_rules, single_pod_rules
    rules = multi_pod_rules() if multi_pod else single_pod_rules()
    if shape.kind == "decode":
        # single-token step: no sequence dim to shard
        rules = rules.with_overrides(act_seq=None)
    return rules


# ---------------------------------------------------------------------------
# MODEL_FLOPS (the roofline's "useful work" yardstick)
# ---------------------------------------------------------------------------

def count_params(cfg: ModelConfig) -> int:
    model = build_model(cfg)
    return sum(int(np.prod(s.shape))
               for s in jax.tree.leaves(model.abstract_params()))


def count_active_params(cfg: ModelConfig) -> int:
    n = count_params(cfg)
    if cfg.moe_experts:
        from repro.models.moe import pad_experts
        E = pad_experts(cfg.moe_experts)
        inactive = (E - cfg.moe_top_k) * 3 * cfg.d_model * cfg.d_ff
        n -= inactive * cfg.n_layers // len(cfg.pattern)
    return n


def model_flops(cfg: ModelConfig, shape: ShapeConfig) -> float:
    """6·N·D for training, 2·N·D for inference (MoE: N_active)."""
    n = count_active_params(cfg)
    if shape.kind == "train":
        return 6.0 * n * shape.global_batch * shape.seq_len
    if shape.kind == "prefill":
        return 2.0 * n * shape.global_batch * shape.seq_len
    return 2.0 * n * shape.global_batch          # one token per sequence
