"""Single-position (decode) attention is contracted grouped by KV head.

``sdpa`` with one query position views q as (B, 1, Hk, G, hd) and meets the
cache as stored, where a multi-position query repeats K/V G times.  These
tests hold the grouped path to the repeat formula, and check that a decode
program carries no G-fold copy of the cache while prefill keeps the repeat.
"""

import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import build_model, get_config
from repro.models.layers import _attn_mask, _softcap, sdpa


def repeat_sdpa(q, k, v, *, q_pos, k_pos, causal, window, softcap,
                k_len_valid):
    """The repeat formula: K/V copied to H heads, scores in float32."""
    B, Sq, H, hd = q.shape
    G = H // k.shape[2]
    k = jnp.repeat(k, G, axis=2).transpose(0, 2, 3, 1)     # (B, H, hd, Sk)
    v = jnp.repeat(v, G, axis=2).transpose(0, 2, 1, 3)     # (B, H, Sk, hd)
    qh = q.transpose(0, 2, 1, 3).astype(jnp.float32)
    s = jnp.einsum("bhcd,bhds->bhcs", qh, k.astype(jnp.float32))
    s = _softcap(s * (1.0 / math.sqrt(hd)), softcap)
    m = _attn_mask(q_pos, k_pos, causal=causal, window=window,
                   k_len_valid=k_len_valid)
    s = jnp.where(m[:, None] if m.ndim == 3 else m, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhcs,bhsd->bhcd", p, v.astype(jnp.float32))
    return o.transpose(0, 2, 1, 3).astype(q.dtype)


@pytest.mark.parametrize("q_dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("window,softcap", [(None, None), (5, None),
                                            (None, 30.0), (5, 30.0)])
@pytest.mark.parametrize("lengths", ["ragged", "uniform"])
@pytest.mark.parametrize("G", [1, 3, 4])
def test_grouped_decode_matches_repeat(G, lengths, window, softcap, q_dtype):
    B, Smax, Hk, hd = 4, 24, 2, 16
    H = Hk * G
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(G), 3)
    # bf16 values throughout; q may also be carried as float32, whose output
    # is then compared in float32
    q = jax.random.normal(kq, (B, 1, H, hd)).astype(jnp.bfloat16)
    q = q.astype(q_dtype)
    k = jax.random.normal(kk, (B, Smax, Hk, hd)).astype(jnp.bfloat16)
    v = jax.random.normal(kv, (B, Smax, Hk, hd)).astype(jnp.bfloat16)
    k_pos = jnp.arange(Smax)
    if lengths == "ragged":     # per-row filled length, 1 and Smax included
        lens = jnp.asarray([1, 7, 13, Smax], jnp.int32)
        q_pos = (lens - 1)[:, None]
    else:                       # scalar length, as in uniform decode
        lens = jnp.int32(11)
        q_pos = (lens - 1 + jnp.arange(1, dtype=jnp.int32))[None, :]
    kw = dict(q_pos=q_pos, k_pos=k_pos, causal=True, window=window,
              softcap=softcap, k_len_valid=lens)
    got = jax.jit(lambda q, k, v: sdpa(q, k, v, **kw))(q, k, v)
    want = jax.jit(lambda q, k, v: repeat_sdpa(q, k, v, **kw))(q, k, v)
    assert got.shape == (B, 1, H, hd) and got.dtype == q.dtype
    # a bf16 output may round either way from float32 sums that differ in
    # the last place: one bf16 step
    rtol = 0 if q_dtype == jnp.float32 else 2.0 ** -7
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), rtol=rtol,
                               atol=1e-6)


# Distinct sizes, so a tensor's dims say which of them it carries.
B, SMAX, PROMPT, HK, G, HD = 5, 40, 8, 2, 3, 16


def _tiny_gqa_lm():
    cfg = get_config("granite-8b").replace(
        n_layers=2, d_model=96, n_heads=HK * G, n_kv_heads=HK, head_dim=HD,
        d_ff=128, vocab=256, q_chunk=16, loss_seq_chunk=None, remat=False)
    model = build_model(cfg)
    return model, model.init(jax.random.PRNGKey(0))


def _f32_shapes(text):
    return [tuple(int(d) for d in dims.rstrip("x").split("x"))
            for dims in re.findall(r"tensor<((?:\d+x)+)f32>", text)]


def _broadcasts_to(text, shape):
    dims = "x".join(str(d) for d in shape)
    return re.search(r"broadcast_in_dim[^\n]*-> tensor<" + dims + "x", text)


def test_decode_program_has_no_grouped_copy_of_the_cache():
    model, params = _tiny_gqa_lm()
    cache = model.init_cache(batch=B, max_len=SMAX)
    tok = jnp.zeros((B, 1), jnp.int32)
    clen = jnp.asarray([0, 3, 9, 20, SMAX - 1], jnp.int32)
    decode = jax.jit(model.decode_step).lower(params, tok, cache,
                                              clen).as_text()
    assert not _broadcasts_to(decode, (B, SMAX, HK, G, HD))
    cache_times_heads = sorted((B, SMAX, HK * G, HD))
    assert not [s for s in _f32_shapes(decode)
                if sorted(s) == cache_times_heads]

    # prefill keeps the repeat, and with it an f32 copy of the cache at H
    # heads
    tokens = jnp.zeros((B, PROMPT), jnp.int32)
    prefill = jax.jit(model.prefill).lower(params, tokens, cache).as_text()
    assert _broadcasts_to(prefill, (B, SMAX, HK, G, HD))
    assert [s for s in _f32_shapes(prefill) if sorted(s) == cache_times_heads]
