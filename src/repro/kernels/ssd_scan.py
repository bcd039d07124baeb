"""Mamba-2 SSD chunked-scan Pallas TPU kernel.

TPU adaptation of the GPU selective-scan (DESIGN.md §6): instead of a
warp-parallel recurrence, the sequence is tiled into chunks of length L.
The grid walks (batch, head) in parallel and chunks *sequentially*; per
step the kernel computes the dense intra-chunk part with three MXU matmuls
((L×N)·(N×L) decay-masked scores, (L×L)·(L×P) output, (N×L)·(L×P) chunk
state) and carries the (N×P) running state in VMEM scratch across chunk
steps — the cross-chunk recurrence costs one rank-1 update per chunk
instead of S sequential steps.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .substrate import pad_axis_to, round_up, tpu_compiler_params


def _kernel(x_ref, a_ref, b_ref, c_ref, y_ref, fin_ref, state_scr, *,
            L: int, nc: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    x = x_ref[...].astype(jnp.float32)               # (L, P)
    la_col = a_ref[...].astype(jnp.float32)          # (L, 1)
    b = b_ref[...].astype(jnp.float32)               # (L, N)
    c = c_ref[...].astype(jnp.float32)               # (L, N)

    # Prefix sums as masked reductions over (L, L): every value stays 2-D,
    # and the sums are exact f32 adds rather than reduced-precision matmuls.
    row = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    causal = row >= col
    la_row = jnp.sum(jnp.where(row == col, la_col, 0.0), axis=0,
                     keepdims=True)                  # (1, L)
    seg_col = jnp.sum(jnp.where(causal, la_row, 0.0), axis=1,
                      keepdims=True)                 # (L, 1): seg_i
    seg_row = jnp.sum(jnp.where(row <= col, la_col, 0.0), axis=0,
                      keepdims=True)                 # (1, L): seg_j
    total = jnp.sum(la_col, axis=0, keepdims=True)   # (1, 1)

    # intra-chunk: scores_ij = c_i·b_j * exp(seg_i - seg_j) for j <= i
    scores = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    decay = jnp.where(causal, jnp.exp(seg_col - seg_row), 0.0)
    y = jax.lax.dot_general(scores * decay, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)

    # inter-chunk: y += exp(seg_i) * c_i · state_in
    state_in = state_scr[...]                        # (N, P)
    y += jnp.exp(seg_col) * jax.lax.dot_general(
        c, state_in, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    # state update: state = state * exp(total) + Σ_j exp(total - seg_j) b_j x_jᵀ
    w = jnp.exp(total - seg_col)                     # (L, 1)
    state_scr[...] = state_in * jnp.exp(total) + jax.lax.dot_general(
        b * w, x, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    y_ref[...] = y.astype(y_ref.dtype)

    @pl.when(ic == nc - 1)
    def _final():
        fin_ref[...] = state_scr[...].astype(fin_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, log_a, b, c, *, chunk=128, interpret=False):
    """x: (B, S, H, P); log_a: (B, S, H); b, c: (B, S, H, N).

    Returns (y: (B, S, H, P), final_state: (B, H, N, P) fp32).

    ``S`` need not divide the chunk length: inputs are zero-padded to the
    next chunk boundary.  Padded steps carry ``log_a = 0`` and ``x = b = 0``,
    so the recurrence ``state <- state·exp(0) + 0`` leaves the final state
    untouched; padded output rows are sliced away.
    """
    B, S, H, P = x.shape
    N = b.shape[-1]
    L = min(chunk, S)
    S_p = round_up(S, L)
    # head-major (B, H, S, ·): each block's last two dims are (L, ·), and
    # log_a rides as an (L, 1) column
    x = pad_axis_to(x, 1, S_p).transpose(0, 2, 1, 3)
    log_a = pad_axis_to(log_a, 1, S_p).transpose(0, 2, 1)[..., None]
    b = pad_axis_to(b, 1, S_p).transpose(0, 2, 1, 3)
    c = pad_axis_to(c, 1, S_p).transpose(0, 2, 1, 3)
    nc = S_p // L

    grid = (B, H, nc)
    kernel = functools.partial(_kernel, L=L, nc=nc)
    y, fin = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, None, L, P), lambda bi, h, ic: (bi, h, ic, 0)),
            pl.BlockSpec((None, None, L, 1), lambda bi, h, ic: (bi, h, ic, 0)),
            pl.BlockSpec((None, None, L, N), lambda bi, h, ic: (bi, h, ic, 0)),
            pl.BlockSpec((None, None, L, N), lambda bi, h, ic: (bi, h, ic, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, None, L, P), lambda bi, h, ic: (bi, h, ic, 0)),
            pl.BlockSpec((None, None, N, P), lambda bi, h, ic: (bi, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            jax.ShapeDtypeStruct((B, H, N, P), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, log_a, b, c)
    y = y.transpose(0, 2, 1, 3)
    return (y[:, :S] if S_p != S else y), fin
