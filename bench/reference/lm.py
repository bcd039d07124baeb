"""Plain reference of a decoder-only LM: token embedding, a stack of
``n_groups`` groups whose sub-layers are the kinds in the configuration's
``pattern`` (each kind is a module ``reference/<kind>.py``), a final
RMSNorm and the tied unembedding.  Granite's multipliers sit where
``granitemoehybrid`` puts them: the embeddings times
``embedding_multiplier``, each sub-layer's output times
``residual_multiplier`` before the residual add, the logits divided by
``logits_scaling`` (each 1 where the configuration leaves it out; the
attention multiplier is ``reference/attn.py``'s).  Float32 throughout (or
the float8 control), one sequence at a time, one layer's weights in float32
at a time.  It imports nothing of the program; it reads the weight arrays
that ``bench/weights.py`` drew."""

from __future__ import annotations

import importlib
import math
from functools import partial

import jax
import jax.numpy as jnp

from reference.numerics import einsum, rmsnorm

# the keys the stack reads: see ``drivers/lm_serving.py`` ``check_config``;
# ``embed_scale`` (embeddings times sqrt(d_model)) is the program's
# embedding multiplier
CHECKS = [("n_layers", "n_layers"), ("d_model", "d_model"),
          ("vocab_padded", "vocab"),
          ("pattern", ("pattern", lambda cfg: list(cfg.group_kinds))),
          ("n_groups", "n_groups", lambda m: m["n_layers"]),
          ("embedding_multiplier",
           ("embed_scale",
            lambda cfg: math.sqrt(cfg.d_model) if cfg.embed_scale
            else getattr(cfg, "embedding_multiplier", 1.0)), 1.0),
          ("residual_multiplier",
           ("residual_multiplier",
            lambda cfg: getattr(cfg, "residual_multiplier", 1.0)), 1.0),
          ("logits_scaling",
           ("logits_scaling",
            lambda cfg: getattr(cfg, "logits_scaling", 1.0)), 1.0)]


def kind_module(kind: str):
    """``reference/<kind>.py``; a kind that has no file is an error."""
    try:
        return importlib.import_module(f"reference.{kind}")
    except ModuleNotFoundError as e:
        raise SystemExit(f"no reference for sub-layer kind {kind!r}: "
                         f"add bench/reference/{kind}.py") from e


def sub_names(model: dict) -> list[tuple[str, str]]:
    return [(f"s{i}_{k}", k) for i, k in enumerate(model["pattern"])]


def hidden(model: dict, w: dict, tokens, mode: str):
    """tokens: (S,) int32 -> final normed hidden states (S, D) float32."""
    x = jnp.take(w["embed"]["table"], tokens, axis=0).astype(jnp.float32)
    x = x * model.get("embedding_multiplier", 1.0)
    r = model.get("residual_multiplier", 1.0)
    subs = [(n, kind_module(k)) for n, k in sub_names(model)]

    def group(x, wg):
        for n, mod in subs:
            x = x + r * mod.apply(model, wg[n], x, mode)
        return x, None

    x, _ = jax.lax.scan(group, x, w["layers"])
    return rmsnorm(w["final_norm"]["scale"], x, model["norm_eps"])


def logits(model: dict, w: dict, tokens, mode: str):
    """(S, vocab_padded) float32 logits over the tied table."""
    out = einsum("sd,vd->sv", hidden(model, w, tokens, mode),
                 w["embed"]["table"], mode)
    return out / model.get("logits_scaling", 1.0)


@partial(jax.jit, static_argnames=("model_items", "control"))
def _gaps(w, tokens, served, *, model_items, control):
    model = dict(model_items)
    model["pattern"] = list(model["pattern"])
    ref = logits(model, w, tokens, "f32")
    best = jnp.max(ref, axis=-1)
    safe = jnp.maximum(served, 0)
    got = jnp.take_along_axis(ref, safe[:, None], axis=-1)[:, 0]
    gap = jnp.where(served >= 0, best - got, 0.0)
    if not control:
        return gap, None
    low = jnp.argmax(logits(model, w, tokens, "fp8"), axis=-1)
    low_gap = best - jnp.take_along_axis(ref, low[:, None], axis=-1)[:, 0]
    return gap, jnp.where(served >= 0, low_gap, 0.0)


def _freeze(model: dict):
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in model.items()
                        if not isinstance(v, dict)))


def token_gaps(model: dict, w: dict, prompt, served, length: int,
               control: bool = False):
    """For one request: the gap by which each served token's reference
    logit lies below the reference's best at its position, and with
    ``control`` the same gap for the token that the float8 reference puts
    first there.  The sequence (prompt, served tokens) is right-padded to
    ``length`` so that every request runs one compiled program; causal
    attention keeps the padding out of every position compared.
    Returns numpy arrays over the served tokens."""
    import numpy as np
    prompt = np.asarray(prompt, np.int32)
    served = np.asarray(served, np.int32)
    seq = np.concatenate([prompt, served[:-1]])
    if len(seq) > length:
        raise ValueError(f"sequence of {len(seq)} exceeds {length}")
    toks = np.zeros(length, np.int32)
    toks[:len(seq)] = seq
    target = np.full(length, -1, np.int32)
    target[len(prompt) - 1:len(seq)] = served
    gap, low = _gaps(w, jnp.asarray(toks), jnp.asarray(target),
                     model_items=_freeze(model), control=control)
    sl = slice(len(prompt) - 1, len(seq))
    gap = np.asarray(gap)[sl]
    return gap, (None if low is None else np.asarray(low)[sl])
