"""Flash attention (prefill) Pallas TPU kernel.

TPU-native design (DESIGN.md §6): the grid iterates (batch, q-head, q-block)
in parallel and the kv-block dimension sequentially ("arbitrary"), keeping
the online-softmax running max/denominator/accumulator in VMEM scratch.
Every matmul is (bq×hd)·(hd×bk) / (bq×bk)·(bk×hd) with 128-aligned tiles so
it lands on the MXU.  GQA is handled by indexing the kv head as
``h // (H // Hk)`` in the k/v BlockSpec index maps — no head replication in
HBM.  Sliding-window and logit-softcap (gemma2) are fused into the score
path.  Causal q-blocks that lie entirely outside the kv block are skipped
via ``pl.when`` (block-level masking).

Uneven sequence lengths are handled by the substrate layer: q/k/v are
zero-padded up to the next block boundary, padded key positions are masked
to ``-inf`` via ``kpos < k_len``, and padded query rows are sliced off the
output.

The wrapper transposes q/k/v to head-major ``(B, H, S, hd)`` so every block
is a ``(rows, hd)`` tile: the TPU compiler requires a block's last two dims
to be multiples of (8, 128) or whole, which a head axis of 1 in the
second-minor place of ``(B, S, H, hd)`` is not.
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


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, causal: bool, window: int | None,
            softcap: float | None, bq: int, bk: int, nk: int, k_len: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = iq * bq
    k_start = ik * bk

    # block-level skip: causal => kv blocks entirely in the future contribute
    # nothing; sliding window => kv blocks entirely before the window too;
    # padding => kv blocks entirely past the true key length.
    relevant = k_start < k_len
    if causal:
        relevant &= k_start <= q_start + bq - 1
    if window is not None:
        relevant &= k_start + bk - 1 >= q_start - window + 1

    @pl.when(relevant)
    def _compute():
        q = q_ref[...].astype(jnp.float32)                 # (bq, hd)
        k = k_ref[...].astype(jnp.float32)                 # (bk, hd)
        v = v_ref[...].astype(jnp.float32)                 # (bk, hd)

        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)

        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = kpos < k_len
        if causal:
            mask &= qpos >= kpos
        if window is not None:
            mask &= qpos - kpos < window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]                                 # (bq, 1)
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
        l = jnp.where(l == 0.0, 1.0, l)    # fully-masked rows -> zeros
        o_ref[...] = (acc_scr[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "softcap",
                                             "block_q", "block_k",
                                             "interpret"))
def flash_attention(q, k, v, *, causal=True, window=None, softcap=None,
                    block_q=128, block_k=128, interpret=False):
    """q: (B, Sq, H, hd); k, v: (B, Sk, Hk, hd) -> (B, Sq, H, hd).

    ``Sq``/``Sk`` need not divide the block sizes: inputs are zero-padded to
    the next block boundary and the pad is masked/sliced away.
    """
    B, Sq, H, hd = q.shape
    Sk, Hk = k.shape[1], k.shape[2]
    group = H // Hk
    bq = min(block_q, Sq)
    bk = min(block_k, Sk)
    Sq_p, Sk_p = round_up(Sq, bq), round_up(Sk, bk)
    # head-major (B, H, S, hd): each block's last two dims are (rows, hd)
    q = pad_axis_to(q, 1, Sq_p).transpose(0, 2, 1, 3)
    k = pad_axis_to(k, 1, Sk_p).transpose(0, 2, 1, 3)
    v = pad_axis_to(v, 1, Sk_p).transpose(0, 2, 1, 3)
    nq, nk = Sq_p // bq, Sk_p // bk
    scale = 1.0 / math.sqrt(hd)

    grid = (B, H, nq, nk)
    kernel = functools.partial(_kernel, scale=scale, causal=causal,
                               window=window, softcap=softcap, bq=bq, bk=bk,
                               nk=nk, k_len=Sk)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, None, bq, hd),
                         lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((None, None, bk, hd),
                         lambda b, h, iq, ik, g=group: (b, h // g, ik, 0)),
            pl.BlockSpec((None, None, bk, hd),
                         lambda b, h, iq, ik, g=group: (b, h // g, ik, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, bq, hd),
                               lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v).transpose(0, 2, 1, 3)
    return out[:, :Sq] if Sq_p != Sq else out
