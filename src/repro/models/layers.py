"""Shared neural-net layers for the architecture zoo (pure JAX, explicit
pytrees).

Parameters are declared with :class:`Pm` leaf specs carrying shape + logical
sharding axes; ``init_tree`` / ``abstract_tree`` / ``axes_tree`` materialise
them.  All activation tensors pass through ``runtime.sharding.constrain`` so
the same code runs unsharded on CPU and SPMD-sharded on the production mesh.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.runtime.sharding import constrain


# ---------------------------------------------------------------------------
# Parameter spec trees
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Pm:
    """Parameter leaf: shape + logical axes (+ init)."""

    shape: tuple[int, ...]
    axes: tuple[str | None, ...]
    init: str = "normal"          # normal | zeros | ones
    scale: float | None = None    # None => 1/sqrt(fan_in)

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def _is_leaf(x):
    return isinstance(x, Pm)


def abstract_tree(spec, dtype=jnp.bfloat16):
    return jax.tree.map(
        lambda p: jax.ShapeDtypeStruct(p.shape, dtype), spec, is_leaf=_is_leaf)


def axes_tree(spec):
    return jax.tree.map(lambda p: p.axes, spec, is_leaf=_is_leaf)


def init_tree(spec, key, dtype=jnp.bfloat16):
    leaves, treedef = jax.tree.flatten(spec, is_leaf=_is_leaf)
    keys = jax.random.split(key, len(leaves))
    out = []
    for p, k in zip(leaves, keys):
        if p.init == "zeros":
            out.append(jnp.zeros(p.shape, dtype))
        elif p.init == "ones":
            out.append(jnp.ones(p.shape, dtype))
        else:
            fan_in = p.shape[0] if p.shape else 1
            scale = p.scale if p.scale is not None else 1.0 / math.sqrt(max(fan_in, 1))
            out.append((jax.random.normal(k, p.shape, jnp.float32) * scale
                        ).astype(dtype))
    return jax.tree.unflatten(treedef, out)


def stack_spec(spec, n: int):
    """Prepend a 'layers' stacking dim to every leaf (scan-over-layers).

    The fan-in-derived init scale is resolved *before* stacking so the extra
    leading dim does not corrupt it.
    """
    def stack(p: Pm) -> Pm:
        scale = p.scale
        if scale is None and p.init == "normal":
            scale = 1.0 / math.sqrt(max(p.shape[0] if p.shape else 1, 1))
        return Pm((n, *p.shape), ("layers", *p.axes), p.init, scale)

    return jax.tree.map(stack, spec, is_leaf=_is_leaf)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def rmsnorm_spec(d: int) -> dict:
    return {"scale": Pm((d,), ("unsharded",), init="zeros")}  # (1+scale) form


def rmsnorm(p, x, eps: float = 1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * (1.0 + p["scale"].astype(jnp.float32))).astype(dt)


def layernorm_spec(d: int) -> dict:
    return {"scale": Pm((d,), ("unsharded",), init="ones"),
            "bias": Pm((d,), ("unsharded",), init="zeros")}


def layernorm(p, x, eps: float = 1e-5):
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32)
            + p["bias"].astype(jnp.float32)).astype(dt)


def make_norm(kind: str, d: int):
    if kind == "rmsnorm":
        return rmsnorm_spec(d), rmsnorm
    if kind == "layernorm":
        return layernorm_spec(d), layernorm
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# Rotary position embedding
# ---------------------------------------------------------------------------

def rope(x, positions, theta: float = 10000.0):
    """x: (..., S, H, hd) ; positions: (..., S) broadcastable."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = jnp.exp(-jnp.arange(0, half, dtype=jnp.float32)
                    * (math.log(theta) / half))
    ang = positions[..., :, None].astype(jnp.float32) * freqs  # (..., S, half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    cos = cos[..., :, None, :]
    sin = sin[..., :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    y1 = x1 * cos - x2 * sin
    y2 = x2 * cos + x1 * sin
    return jnp.concatenate([y1, y2], axis=-1).astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention (GQA, sliding window, logit softcap) — chunked jnp path.
# The Pallas kernels in repro.kernels implement the same math for TPU; the
# jnp path here is the oracle and the dry-run lowering target.
# ---------------------------------------------------------------------------

def attention_spec(d_model: int, n_heads: int, n_kv: int, head_dim: int,
                   qkv_bias: bool = False) -> dict:
    spec = {
        "wq": Pm((d_model, n_heads, head_dim), ("embed", "heads", "head_dim")),
        "wk": Pm((d_model, n_kv, head_dim), ("embed", "kv_heads", "head_dim")),
        "wv": Pm((d_model, n_kv, head_dim), ("embed", "kv_heads", "head_dim")),
        "wo": Pm((n_heads, head_dim, d_model), ("heads", "head_dim", "embed")),
    }
    if qkv_bias:
        spec["bq"] = Pm((n_heads, head_dim), ("heads", "head_dim"), init="zeros")
        spec["bk"] = Pm((n_kv, head_dim), ("kv_heads", "head_dim"), init="zeros")
        spec["bv"] = Pm((n_kv, head_dim), ("kv_heads", "head_dim"), init="zeros")
    return spec


def _softcap(x, cap):
    if cap is None:
        return x
    return cap * jnp.tanh(x / cap)


def _attn_mask(q_pos, k_pos, *, causal: bool, window: int | None,
               k_len_valid=None):
    """(..., Sq, Sk) boolean mask of allowed attention.

    ``k_len_valid`` may be a scalar or a per-row (B,) vector (ragged decode
    batches in the serving engine)."""
    m = jnp.ones((q_pos.shape[-1], k_pos.shape[-1]), dtype=bool)
    d = q_pos[..., :, None] - k_pos[..., None, :]
    if causal:
        m = m & (d >= 0)
    if window is not None:
        m = m & (d < window)
    if k_len_valid is not None:
        lv = jnp.asarray(k_len_valid)
        if lv.ndim == 1:
            lv = lv[:, None, None]
        m = m & (k_pos[..., None, :] < lv)
    return m


def sdpa(q, k, v, *, q_pos, k_pos, causal=True, window=None, softcap=None,
         k_len_valid=None, q_chunk: int | None = None):
    """Scaled dot-product attention with GQA.

    q: (B, Sq, H, hd) ; k, v: (B, Sk, Hk, hd).  Chunked over Sq so the score
    matrix never exceeds (B, H, q_chunk, Sk) — required for 32k prefill.
    Softmax in fp32.

    ``q_chunk=None`` (the default) resolves to the autotuned
    ``flash_attention`` ``block_q`` winner when a tuned BenchmarkDB has
    been adopted (``kernels/substrate.adopt_tuned_params``) — the serving
    path then chunks at the same granularity the cost model priced — and
    to 512 otherwise.

    A single-position query (``Sq == 1``, the decode step) is contracted
    grouped by KV head: q is viewed as (B, 1, Hk, G, hd) and meets K/V as
    stored, so no G-fold copy of the cache is made.  Head h reads KV head
    h // G, the order ``jnp.repeat`` gives.  Its scores are only
    (B, Hk, G, 1, Sk), small enough that how they shard does not matter.

    A multi-position query repeats K/V to H heads instead: the repeated dim
    then shards cleanly over the TP axis, whereas a grouped (Hk, G) einsum
    forces XLA into involuntary resharding (observed: replicated
    (B,Hk,G,C,Sk) score tensors blowing past HBM on starcoder2/internvl2 at
    long chunked prefill — EXPERIMENTS.md §Perf).
    """
    if q_chunk is None:
        from repro.kernels.substrate import serving_param
        q_chunk = serving_param("flash_attention", "block_q", 512)
    B, Sq, H, hd = q.shape
    Hk = k.shape[2]
    G = H // Hk
    scale = 1.0 / math.sqrt(hd)

    if Sq == 1:
        return _sdpa_grouped_single(q, k, v, q_pos=q_pos, k_pos=k_pos,
                                    causal=causal, window=window,
                                    softcap=softcap, k_len_valid=k_len_valid,
                                    scale=scale)

    if G > 1:
        k = jnp.repeat(k, G, axis=2)        # (B, Sk, H, hd)
        v = jnp.repeat(v, G, axis=2)
    kt = k.transpose(0, 2, 3, 1)            # (B, H, hd, Sk)
    vt = v.transpose(0, 2, 1, 3)            # (B, H, Sk, hd)
    # NOTE: no sharding constraint here — decode-mode KV caches may be
    # sequence-sharded (flash-decoding split) while prefill K/V are
    # head-sharded; the cache/input sharding propagates through.

    def one_chunk(qc, qp):
        C = qc.shape[1]
        qh = qc.transpose(0, 2, 1, 3)       # (B, H, C, hd)
        s = jnp.einsum("bhcd,bhds->bhcs", qh.astype(jnp.float32),
                       kt.astype(jnp.float32)) * scale
        s = _softcap(s, softcap)
        m = _attn_mask(qp, k_pos, causal=causal, window=window,
                       k_len_valid=k_len_valid)
        s = jnp.where(m[:, None] if m.ndim == 3 else m, s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhcs,bhsd->bhcd", p, vt.astype(jnp.float32))
        return o.transpose(0, 2, 1, 3).astype(q.dtype)

    if Sq <= q_chunk or Sq % q_chunk != 0:
        return one_chunk(q, q_pos)

    n = Sq // q_chunk
    qs = q.reshape(B, n, q_chunk, H, hd).transpose(1, 0, 2, 3, 4)
    ps = q_pos.reshape(*q_pos.shape[:-1], n, q_chunk)
    ps = jnp.moveaxis(ps, -2, 0)

    def body(_, qp):
        return None, one_chunk(*qp)

    # flash-attention-style recompute: don't let scan's backward save the
    # (B,Hk,G,chunk,Sk) probability residuals of every chunk
    _, outs = jax.lax.scan(jax.checkpoint(body), None, (qs, ps))
    return outs.transpose(1, 0, 2, 3, 4).reshape(B, Sq, H, hd)


def _sdpa_grouped_single(q, k, v, *, q_pos, k_pos, causal, window, softcap,
                         k_len_valid, scale):
    """``sdpa`` for one query position, contracted grouped by KV head.

    Same float32 arithmetic as the repeat path: bf16 K/V are widened inside
    the contractions, and HIGHEST precision keeps an MXU lowering from
    rounding any float32 operand to bf16."""
    B, _, H, hd = q.shape
    Hk = k.shape[2]
    G = H // Hk
    hi = jax.lax.Precision.HIGHEST
    qg = q.reshape(B, 1, Hk, G, hd).astype(jnp.float32)
    s = jnp.einsum("bckgd,bskd->bkgcs", qg, k.astype(jnp.float32),
                   precision=hi) * scale
    s = _softcap(s, softcap)
    m = _attn_mask(q_pos, k_pos, causal=causal, window=window,
                   k_len_valid=k_len_valid)
    s = jnp.where(m[:, None, None] if m.ndim == 3 else m, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgcs,bskd->bckgd", p, v.astype(jnp.float32),
                   precision=hi)
    return o.reshape(B, 1, H, hd).astype(q.dtype)


def attention(p, x, *, positions, rope_theta=10000.0, causal=True,
              window=None, softcap=None, kv_cache=None, cache_len=None,
              use_rope=True, q_chunk=None, query_pre_attn_scalar=None):
    """Full attention sub-layer: qkv proj -> rope -> sdpa -> out proj.

    ``kv_cache``: None (training/prefill over x itself) or dict with
    "k","v" of shape (B, Smax, Hk, hd) plus ``cache_len`` — decode mode:
    x is the new token(s), cache is updated at ``cache_len``.
    Returns (out, new_cache).
    """
    B, S, D = x.shape
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if "bq" in p:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    if query_pre_attn_scalar is not None:
        # gemma-style: scale q by 1/sqrt(s) instead of 1/sqrt(hd); fold in the
        # ratio so sdpa's 1/sqrt(hd) combines to 1/sqrt(s).
        hd = q.shape[-1]
        q = q * math.sqrt(hd / query_pre_attn_scalar)
    if use_rope:
        q = rope(q, positions, rope_theta)
        k = rope(k, positions, rope_theta)
    q = constrain(q, "act_batch", None, "act_heads", None)

    if kv_cache is None:
        out = sdpa(q, k, v, q_pos=positions, k_pos=positions, causal=causal,
                   window=window, softcap=softcap, q_chunk=q_chunk)
        new_cache = None
    else:
        clen = jnp.asarray(cache_len)
        if clen.ndim == 1:      # ragged decode: per-row write offsets
            upd = jax.vmap(
                lambda c, new, start: jax.lax.dynamic_update_slice_in_dim(
                    c, new, start, axis=0))
            ck = upd(kv_cache["k"], k.astype(kv_cache["k"].dtype), clen)
            cv = upd(kv_cache["v"], v.astype(kv_cache["v"].dtype), clen)
        else:
            ck = jax.lax.dynamic_update_slice_in_dim(
                kv_cache["k"], k.astype(kv_cache["k"].dtype), cache_len,
                axis=1)
            cv = jax.lax.dynamic_update_slice_in_dim(
                kv_cache["v"], v.astype(kv_cache["v"].dtype), cache_len,
                axis=1)
        Smax = ck.shape[1]
        k_pos = jnp.arange(Smax)
        out = sdpa(q, ck, cv, q_pos=positions, k_pos=k_pos, causal=causal,
                   window=window, softcap=softcap,
                   k_len_valid=cache_len + S, q_chunk=q_chunk)
        new_cache = {"k": ck, "v": cv}

    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    y = constrain(y, "act_batch", "act_seq", None)
    return y, new_cache


def attention_cache_spec(cfg, batch: int, max_len: int,
                         kv_seq_axis: str = "act_kv_seq"):
    """ShapeDtypeStruct + logical axes for one layer's KV cache."""
    shape = (batch, max_len, cfg.n_kv_heads, cfg.head_dim)
    heads_shardable = cfg.n_kv_heads % 16 == 0
    if heads_shardable:
        axes = ("act_batch", None, "act_heads", None)
    else:
        axes = ("act_batch", kv_seq_axis, None, None)
    return jax.ShapeDtypeStruct(shape, jnp.bfloat16), axes


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def mlp_spec(d_model: int, d_ff: int, gated: bool) -> dict:
    spec = {"w_up": Pm((d_model, d_ff), ("embed", "ff")),
            "w_down": Pm((d_ff, d_model), ("ff", "embed"))}
    if gated:
        spec["w_gate"] = Pm((d_model, d_ff), ("embed", "ff"))
    return spec


def mlp(p, x, activation: str = "gelu"):
    act = {"gelu": partial(jax.nn.gelu, approximate=True),
           "silu": jax.nn.silu, "relu": jax.nn.relu}[activation]
    h = jnp.einsum("bsd,df->bsf", x, p["w_up"])
    if "w_gate" in p:
        g = jnp.einsum("bsd,df->bsf", x, p["w_gate"])
        h = act(g) * h
    else:
        h = act(h)
    h = constrain(h, "act_batch", None, "act_ff")
    y = jnp.einsum("bsf,fd->bsd", h, p["w_down"])
    return constrain(y, "act_batch", "act_seq", None)


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------

def embed_spec(vocab: int, d_model: int) -> dict:
    # 1/sqrt(d) keeps tied-unembedding logits O(1) after the final norm.
    # The embed dim is deliberately NOT FSDP-sharded: a ('model','data')
    # table makes the unembed backward all-gather the full (B,c,V) logit
    # cotangent on every chip (XLA must reshard the table grad to 'data' on
    # d while 'data' is busy on the contraction) — observed +29 GiB/chip.
    # Vocab over TP alone keeps the table at vocab/16 per chip.
    return {"table": Pm((vocab, d_model), ("vocab", "unsharded"),
                        scale=1.0 / math.sqrt(d_model))}


def embed(p, tokens, scale_by_dim: bool = False):
    # identity constraint matching the table's own sharding: free in the
    # forward, but it pins the COTANGENT sharding in the backward — without
    # it the gather-bwd scatter materialises the full (V, D) fp32 table
    # gradient replicated on every chip (observed ~17 GiB on gemma2 train)
    t = constrain(p["table"], "act_vocab", None)
    x = jnp.take(t, tokens, axis=0)
    if scale_by_dim:
        x = x * math.sqrt(p["table"].shape[1])
    return constrain(x, "act_batch", "act_seq", None)


def unembed(p, x, softcap: float | None = None, scaling: float = 1.0):
    """Logits over the tied table, divided by ``scaling`` (granite's
    ``logits_scaling``; nothing is emitted at 1.0), then soft-capped."""
    logits = jnp.einsum("bsd,vd->bsv", x, p["table"]).astype(jnp.float32)
    if scaling != 1.0:
        logits = logits / scaling
    logits = _softcap(logits, softcap)
    # TP layout: batch over data, vocab over model (seq stays unsharded —
    # it is already chunked by the loss and the vocab dim carries the TP).
    return constrain(logits, "act_batch", None, "act_vocab")


# ---------------------------------------------------------------------------
# Loss (chunked over sequence so (B,S,V) logits never fully materialise)
# ---------------------------------------------------------------------------

def cross_entropy_loss(embed_params, x, labels, *, softcap=None,
                       seq_chunk: int | None = None, scaling: float = 1.0):
    """x: (B, S, D) final hidden; labels: (B, S) int32; returns mean nll.

    ``seq_chunk`` bounds the materialised logits to (B, chunk, V).

    The gold-label logit is computed as ⟨x, table[label]⟩ — NOT via
    ``take_along_axis`` on the logits: indexing the vocab-sharded logits
    makes XLA all-gather them to every chip (observed 7.3 GiB/chip × several
    copies on internvl2 train — EXPERIMENTS.md §Perf).  The logsumexp runs
    on the vocab-sharded logits (partial reductions + small all-reduce).
    """
    B, S, D = x.shape

    def chunk_loss(xc, yc):
        logits = unembed(embed_params, xc, softcap=softcap,
                         scaling=scaling)
        logz = jax.nn.logsumexp(logits, axis=-1)
        # gold logit via a vocab-iota mask: the masked sum reduces the
        # *sharded* vocab axis locally + a tiny all-reduce, whereas
        # take_along_axis would all-gather the full logits to every chip
        mask = yc[..., None] == jax.lax.broadcasted_iota(
            jnp.int32, logits.shape, 2)
        gold = jnp.sum(jnp.where(mask, logits, 0.0), axis=-1)
        valid = yc >= 0                     # label -1 = ignore (VLM prefix)
        return jnp.sum(jnp.where(valid, logz - gold, 0.0)), \
            jnp.sum(valid.astype(jnp.float32))

    # gather the sequence dim once in bf16 (the chunked loss consumes
    # seq-contiguous blocks; leaving the SP sharding in place makes XLA
    # keep fp32 full-sequence cotangent copies around the reshape)
    x = constrain(x, "act_batch", None, None)
    if seq_chunk is not None and S % seq_chunk != 0:
        # largest divisor of S not exceeding the requested chunk
        seq_chunk = next(c for c in range(min(seq_chunk, S), 0, -1)
                         if S % c == 0)
    if seq_chunk is None or S <= seq_chunk:
        total, count = chunk_loss(x, labels)
    else:
        n = S // seq_chunk
        xs = x.reshape(B, n, seq_chunk, D).transpose(1, 0, 2, 3)
        ys = labels.reshape(B, n, seq_chunk).transpose(1, 0, 2)

        def body(acc, xy):
            t, c = chunk_loss(*xy)
            return (acc[0] + t, acc[1] + c), None

        # recompute (B, chunk, V) logits per chunk in the backward
        (total, count), _ = jax.lax.scan(
            jax.checkpoint(body), (jnp.float32(0.0), jnp.float32(0.0)),
            (xs, ys))
    return total / jnp.maximum(count, 1.0)
