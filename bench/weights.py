"""Random weights for a configuration, drawn from the run's seed on the
device in one jitted call, in the type they are served in (bfloat16).

The tree is laid out as the program takes it (its names and shapes come
from ``jax.eval_shape`` of the program's init, and are checked against
it); every number in it is drawn here, by rules of the reference's kind
modules, so the reference reads weights that the benchmark made."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from reference.lm import kind_module

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def _path(path) -> list[str]:
    return [getattr(p, "key", getattr(p, "name", str(p))) for p in path]


def leaf_rule(path: list[str], shape, model: dict):
    """(std, padding) of one leaf: ``padding`` is (axis, start) of a tail
    drawn as zeros, or None."""
    if path[0] == "embed":
        return 1.0 / math.sqrt(model["d_model"]), (0, model["vocab"])
    if path[0] == "final_norm":
        return 0.0, None
    if path[0] != "layers":
        raise SystemExit(f"no weight rule for leaf {'/'.join(path)}")
    kind = path[1].split("_", 1)[1]
    mod = kind_module(kind)
    leaf = "/".join(path[2:])
    inner = tuple(shape[1:])                  # without the layer axis
    pad = getattr(mod, "padding", lambda *_: None)(leaf, model)
    if pad is not None:
        pad = (pad[0] + 1, pad[1])            # past the layer axis
    return mod.init_scale(leaf, inner, model), pad


def make(template, model: dict, key):
    """Draw every leaf of ``template`` (a tree of ShapeDtypeStructs)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(template)
    rules = [leaf_rule(_path(p), s.shape, model) for p, s in flat]
    dtype = DTYPES[model["dtype"]]

    @jax.jit
    def draw(key):
        out = []
        for i, ((_, s), (std, pad)) in enumerate(zip(flat, rules)):
            if std == 0.0:
                out.append(jnp.zeros(s.shape, dtype))
                continue
            x = jax.random.normal(jax.random.fold_in(key, i), s.shape,
                                  dtype) * jnp.asarray(std, dtype)
            if pad is not None:
                axis, start = pad
                keep = jax.lax.broadcasted_iota(jnp.int32, s.shape,
                                                axis) < start
                x = jnp.where(keep, x, jnp.zeros((), dtype))
            out.append(x)
        return out

    leaves = draw(key)
    for (p, s), x in zip(flat, leaves):
        if x.shape != s.shape or x.dtype != s.dtype:
            raise SystemExit(f"leaf {'/'.join(_path(p))}: drew {x.shape} "
                             f"{x.dtype}, the program takes {s.shape} "
                             f"{s.dtype}")
    return jax.tree_util.tree_unflatten(treedef, leaves)
