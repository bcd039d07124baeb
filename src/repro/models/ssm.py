"""Mamba-2 (SSD) blocks — TPU-native chunked formulation.

The GPU reference implements the selective scan with a fused warp-level
kernel; the TPU-idiomatic equivalent (per DESIGN.md §2) is the SSD *chunked*
algorithm: the sequence is split into chunks of length ``L``; within a chunk
the recurrence unrolls into dense (L×L) matmuls that map onto the MXU, and
only a small per-chunk state recurrence crosses chunks (lax.scan over
S/L steps).  ``repro.kernels.ssd_scan`` provides the Pallas kernel for the
chunk-local part; this module is the pure-jnp oracle and the dry-run path.

The same ``ssd()`` primitive also powers the xLSTM mLSTM block (mLSTM is an
SSD with forget-gate decays and input-gate injection — see models/xlstm.py).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.runtime.sharding import constrain
from .layers import Pm, rmsnorm, rmsnorm_spec


# ---------------------------------------------------------------------------
# SSD core: y = SSD(x, a, b, c) with per-(position, head) scalar decay
# ---------------------------------------------------------------------------

def ssd(x, log_a, b, c, *, chunk: int | None = None, initial_state=None,
        unroll: bool = False):
    """Chunked state-space duality scan.

    x:      (B, S, H, P)    inputs (already gated / dt-scaled)
    log_a:  (B, S, H)       per-step log decay (<= 0)
    b:      (B, S, Hb, N)   input maps  ("K"); Hb == H, or Hb == 1 for
    c:      (B, S, Hb, N)   head-shared maps (Mamba-2 ngroups=1 — kept
                            un-broadcast so the scan xs stay O(B·S·N))
    returns (y: (B, S, H, P), final_state: (B, H, N, P))

    ``chunk=None`` (the default) resolves to the autotuned ``ssd_scan``
    ``chunk`` winner when a tuned BenchmarkDB has been adopted
    (``kernels/substrate.adopt_tuned_params``), and to 128 otherwise;
    model configs that pin ``ssm_chunk`` keep passing it explicitly.
    """
    if chunk is None:
        from repro.kernels.substrate import serving_param
        chunk = serving_param("ssd_scan", "chunk", 128)
    B, S, H, P = x.shape
    Hb, N = b.shape[-2], b.shape[-1]
    shared = Hb == 1
    L = min(chunk, S)
    if S % L:
        # whole chunks: the tail is padded with no decay and no input, so
        # the state passes through it unchanged
        def pad(t):
            return jnp.pad(t, [(0, 0), (0, L - S % L)]
                           + [(0, 0)] * (t.ndim - 2))
        y, final = ssd(pad(x), pad(log_a), pad(b), pad(c), chunk=L,
                       initial_state=initial_state, unroll=unroll)
        return y[:, :S], final
    nc = S // L

    xc = x.reshape(B, nc, L, H, P).transpose(1, 0, 2, 3, 4)
    ac = log_a.reshape(B, nc, L, H).astype(jnp.float32).transpose(1, 0, 2, 3)
    bc = b.reshape(B, nc, L, Hb, N).transpose(1, 0, 2, 3, 4)
    cc = c.reshape(B, nc, L, Hb, N).transpose(1, 0, 2, 3, 4)

    if initial_state is None:
        initial_state = jnp.zeros((B, H, N, P), jnp.float32)

    causal = jnp.tril(jnp.ones((L, L), bool))

    # One chunk per scan step: the (L, L, H) decay/score tensors live only
    # inside the step body, bounding peak memory to O(B·L·L·H) instead of
    # O(B·S·L·H) (which blew past HBM at train_4k batch 256 — see
    # EXPERIMENTS.md §Perf).
    def step(state, inputs):
        xu, au, bu, cu = inputs                         # (B,L,H,*) per chunk
        seg = jnp.cumsum(au, axis=1)                    # (B, L, H)
        total = seg[:, -1]                              # (B, H)

        # intra-chunk: D[i,j] = exp(seg_i - seg_j) for j <= i
        diff = seg[:, :, None, :] - seg[:, None, :, :]  # (B, L, L, H)
        decay = jnp.where(causal[None, :, :, None], jnp.exp(diff), 0.0)
        bf = bu.astype(jnp.float32)
        cf = cu.astype(jnp.float32)
        if shared:
            scores = jnp.einsum("bin,bjn->bij", cf[:, :, 0], bf[:, :, 0])
            m = scores[..., None] * decay               # (B, L, L, H)
        else:
            scores = jnp.einsum("bihn,bjhn->bijh", cf, bf)
            m = scores * decay
        y = jnp.einsum("bijh,bjhp->bihp", m.astype(xu.dtype), xu)

        # inter-chunk contribution of the carried state
        if shared:
            y = y + jnp.einsum("bin,bih,bhnp->bihp", cf[:, :, 0],
                               jnp.exp(seg), state).astype(xu.dtype)
        else:
            y = y + jnp.einsum("bihn,bih,bhnp->bihp", cf, jnp.exp(seg),
                               state).astype(xu.dtype)

        # state update
        w = jnp.exp(total[:, None, :] - seg)            # (B, L, H)
        if shared:
            state_c = jnp.einsum("bln,blh,blhp->bhnp", bf[:, :, 0], w,
                                 xu.astype(jnp.float32))
        else:
            state_c = jnp.einsum("blhn,blh,blhp->bhnp", bf, w,
                                 xu.astype(jnp.float32))
        state = state * jnp.exp(total)[:, :, None, None] + state_c
        return state, y

    # checkpoint the chunk body: without it, scan's backward saves the
    # (B,L,L,H) decay/score residuals for EVERY chunk (observed 128 GiB/chip
    # on zamba2 train_4k); with it, each chunk recomputes them in the bwd.
    # ``unroll`` is used by the dry-run costing variants only (XLA cost
    # analysis ignores while-loop trip counts).
    final, ys = jax.lax.scan(jax.checkpoint(step), initial_state,
                             (xc, ac, bc, cc), unroll=nc if unroll else 1)
    y = ys.transpose(1, 0, 2, 3, 4).reshape(B, S, H, P)
    return y, final


def ssd_decode_step(state, x, log_a, b, c):
    """One-token recurrent update.  x: (B,1,H,P) etc.  Returns (y, state)."""
    a = jnp.exp(log_a[:, 0].astype(jnp.float32))        # (B, H)
    st = state * a[:, :, None, None] + jnp.einsum(
        "bhn,bhp->bhnp", b[:, 0].astype(jnp.float32),
        x[:, 0].astype(jnp.float32))
    y = jnp.einsum("bhn,bhnp->bhp", c[:, 0].astype(jnp.float32), st)
    return y[:, None].astype(x.dtype), st


# ---------------------------------------------------------------------------
# Mamba-2 block
# ---------------------------------------------------------------------------

def mamba2_dims(cfg):
    d_inner = cfg.ssm_expand * cfg.d_model
    nheads = d_inner // cfg.ssm_head_dim
    conv_dim = d_inner + 2 * cfg.ssm_state
    return d_inner, nheads, conv_dim


def mamba2_spec(cfg) -> dict:
    d = cfg.d_model
    d_inner, H, conv_dim = mamba2_dims(cfg)
    N = cfg.ssm_state
    return {
        # fused input projection: [z, x, B, C, dt]
        "w_in": Pm((d, 2 * d_inner + 2 * N + H), ("embed", "ff")),
        "conv_w": Pm((cfg.ssm_conv, conv_dim), ("conv", "ff"), scale=0.5),
        "conv_b": Pm((conv_dim,), ("ff",), init="zeros"),
        "a_log": Pm((H,), ("heads",), init="zeros"),
        "d_skip": Pm((H,), ("heads",), init="ones"),
        "dt_bias": Pm((H,), ("heads",), init="zeros"),
        "norm": rmsnorm_spec(d_inner),
        "w_out": Pm((d_inner, d), ("ff", "embed")),
    }


def _split_in(cfg, h):
    d_inner, H, _ = mamba2_dims(cfg)
    N = cfg.ssm_state
    z, xbc_dt = jnp.split(h, [d_inner], axis=-1)
    xbc, dt = jnp.split(xbc_dt, [d_inner + 2 * N], axis=-1)
    return z, xbc, dt


def _causal_conv(w, bias, x, state=None, lengths=None):
    """Depthwise causal conv1d.  x: (B, S, C); state: (B, K-1, C) or None.
    Returns (y, new_state): the last K-1 inputs, or with ``lengths`` (B,)
    each row's last K-1 inputs before its length, ``xp[len:len+K-1]`` of
    the input behind its state (zeros where the row is shorter)."""
    K = w.shape[0]
    if state is None:
        pad = jnp.zeros((x.shape[0], K - 1, x.shape[2]), x.dtype)
    else:
        pad = state.astype(x.dtype)
    xp = jnp.concatenate([pad, x], axis=1)
    y = sum(xp[:, i:i + x.shape[1]] * w[i] for i in range(K))
    if K == 1:
        new_state = None
    elif lengths is None:
        new_state = xp[:, -(K - 1):]
    else:
        new_state = jax.vmap(lambda r, n: jax.lax.dynamic_slice_in_dim(
            r, n, K - 1, axis=0))(xp, lengths)
    return jax.nn.silu(y + bias), new_state


def gated_rmsnorm(p, y, z, eps: float):
    """Mamba-2's gated norm (``MambaRMSNormGated``): ``rmsnorm(y *
    silu(z))`` over all of d_inner (one group), the gate applied before
    the norm and in float32."""
    g = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    return rmsnorm(p, g, eps=eps).astype(y.dtype)


def mamba2(p, cfg, x, *, state=None, conv_state=None, decode=False,
           lengths=None):
    """x: (B, S, D) -> (y, (ssm_state, conv_state)).

    ``decode=True`` runs the O(1) recurrent update (S == 1 expected);
    otherwise the chunked SSD scan (training / prefill).  ``lengths``
    (B,), prefill of a right-padded batch: at positions at or past a row's
    length ``dt`` is 0, so the decay is 1 and nothing enters the state,
    and both states are those of an exact prefill of the row's length.
    """
    B, S, D = x.shape
    d_inner, H, conv_dim = mamba2_dims(cfg)
    N = cfg.ssm_state
    P = cfg.ssm_head_dim

    h = jnp.einsum("bsd,df->bsf", x, p["w_in"])
    h = constrain(h, "act_batch", None, "act_ff")
    z, xbc, dt = _split_in(cfg, h)
    xbc, new_conv = _causal_conv(p["conv_w"], p["conv_b"], xbc,
                                 state=conv_state, lengths=lengths)
    xs, bc = jnp.split(xbc, [d_inner], axis=-1)
    b_in, c_out = jnp.split(bc, 2, axis=-1)             # (B,S,N) each

    dt = jax.nn.softplus(dt.astype(jnp.float32)
                         + p["dt_bias"].astype(jnp.float32))  # (B,S,H)
    if lengths is not None:
        real = jnp.arange(S)[None, :] < lengths[:, None]
        dt = jnp.where(real[..., None], dt, 0.0)
    a = -jnp.exp(p["a_log"].astype(jnp.float32))        # (H,) negative
    log_a = dt * a                                      # (B,S,H)

    xh = xs.reshape(B, S, H, P) * dt[..., None].astype(xs.dtype)

    if decode:
        if state is None:
            state = jnp.zeros((B, H, N, P), jnp.float32)
        bh = jnp.broadcast_to(b_in[:, :, None, :], (B, S, H, N))
        ch = jnp.broadcast_to(c_out[:, :, None, :], (B, S, H, N))
        y, new_state = ssd_decode_step(state, xh, log_a, bh, ch)
    else:
        # B/C are shared across heads (ngroups=1): pass un-broadcast so the
        # chunk-scan xs stay O(B·S·N), not O(B·S·H·N)
        y, new_state = ssd(xh, log_a, b_in[:, :, None, :],
                           c_out[:, :, None, :], chunk=cfg.ssm_chunk,
                           initial_state=state,
                           unroll=getattr(cfg, "unroll_scans", False))

    y = y + xs.reshape(B, S, H, P) * p["d_skip"].astype(xs.dtype)[:, None]
    y = gated_rmsnorm(p["norm"], y.reshape(B, S, d_inner), z,
                      eps=cfg.norm_eps)
    out = jnp.einsum("bsf,fd->bsd", y, p["w_out"])
    return constrain(out, "act_batch", "act_seq", None), (new_state, new_conv)


def mamba2_state_specs(cfg, batch: int):
    d_inner, H, conv_dim = mamba2_dims(cfg)
    ssm = jax.ShapeDtypeStruct((batch, H, cfg.ssm_state, cfg.ssm_head_dim),
                               jnp.float32)
    conv = jax.ShapeDtypeStruct((batch, cfg.ssm_conv - 1, conv_dim),
                                jnp.bfloat16)
    ssm_axes = ("act_batch", "act_heads", None, None)
    conv_axes = ("act_batch", None, "act_ff")
    return (ssm, ssm_axes), (conv, conv_axes)
