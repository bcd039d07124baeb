"""Decode attention (single new token vs. a long KV cache) Pallas TPU kernel.

Flash-decoding adaptation for TPU: the KV sequence is the *sequential* grid
dimension; each step loads a (bk × hd) cache tile into VMEM, updates the
online-softmax accumulators for every (batch, head) pair, and masks tile
entries beyond the valid cache length.  The query row for a head stays
resident in VMEM across all KV tiles, so HBM traffic is exactly one pass
over the cache — the decode roofline's memory term.  Across chips the cache
is sequence-sharded and XLA combines per-shard partial softmaxes (see
models/layers.py); this kernel is the per-shard worker.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .substrate import pad_axis_to, round_up, tpu_compiler_params

NEG_INF = -1e30


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, softcap: float | None, bk: int, nk: int, G: int):
    b = pl.program_id(0)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = len_ref[b]
    k_start = ik * bk

    @pl.when(k_start < length)
    def _compute():
        q = q_ref[...].astype(jnp.float32)                 # (G, hd)
        k = k_ref[...].astype(jnp.float32)                 # (bk, hd)
        v = v_ref[...].astype(jnp.float32)                 # (bk, hd)

        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)            # (G, bk)

        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        s = jnp.where(kpos < length, s, NEG_INF)

        m_prev = m_scr[...]                                 # (G, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        l = l_scr[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[...] = (acc_scr[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("softcap", "block_k",
                                             "interpret"))
def decode_attention(q, k, v, lengths, *, softcap=None, block_k=256,
                     interpret=False):
    """q: (B, H, hd); k, v: (B, Smax, Hk, hd); lengths: (B,) int32.

    Returns (B, H, hd).  All q heads of one kv group are processed together
    as the (G, hd) left operand of each MXU matmul.

    ``Smax`` need not divide ``block_k``: the cache is zero-padded to the
    next block boundary; padded positions sit past every ``lengths[b]`` and
    are masked by the existing ``kpos < length`` guard.
    """
    B, H, hd = q.shape
    Smax, Hk = k.shape[1], k.shape[2]
    G = H // Hk
    bk = min(block_k, Smax)
    Smax_p = round_up(Smax, bk)
    # head-major (B, Hk, Smax, hd): each cache tile's last two dims are
    # (bk, hd)
    k = pad_axis_to(k, 1, Smax_p).transpose(0, 2, 1, 3)
    v = pad_axis_to(v, 1, Smax_p).transpose(0, 2, 1, 3)
    nk = Smax_p // bk
    scale = 1.0 / math.sqrt(hd)

    qg = q.reshape(B, Hk, G, hd)
    grid = (B, Hk, nk)
    kernel = functools.partial(_kernel, scale=scale, softcap=softcap, bk=bk,
                               nk=nk, G=G)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),          # lengths (B,)
            pl.BlockSpec((None, None, G, hd), lambda b, h, ik: (b, h, 0, 0)),
            pl.BlockSpec((None, None, bk, hd), lambda b, h, ik: (b, h, ik, 0)),
            pl.BlockSpec((None, None, bk, hd), lambda b, h, ik: (b, h, ik, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, G, hd),
                               lambda b, h, ik: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hk, G, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, hd), jnp.float32),
        ],
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(lengths, qg, k, v)
    return out.reshape(B, H, hd)
