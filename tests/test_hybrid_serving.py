"""A Mamba-2 + attention hybrid served through the bucketed path: the
masked prefill against the exact one, the engine's tokens against the
exact path's, Mamba-2's gated norm, Granite's multipliers, and the pool's
byte counters on ``serving.step``."""

import glob
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.models import build_model, get_config
from repro.models import layers as L
from repro.models.ssm import gated_rmsnorm, ssd
from repro.serving import Request, ServingEngine
from repro.serving.queues import row_bucket

MAX_LEN = 64
# two Mamba-2 layers and one attention layer a group, each with an MLP
PATTERN = (("mamba2", "mlp"), ("mamba2", "mlp"), ("attn", "mlp"))


def hybrid_cfg(**kw):
    return get_config("granite-4.0-h-micro").replace(
        n_layers=3, pattern=PATTERN, d_model=64, n_heads=4, n_kv_heads=2,
        head_dim=16, d_ff=96, vocab=128, ssm_state=8, ssm_head_dim=8,
        ssm_chunk=8, q_chunk=32, remat=False, loss_seq_chunk=None, **kw)


@pytest.fixture(scope="module")
def hybrid():
    cfg = hybrid_cfg()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0), jnp.float32)
    return cfg, model, params


def _prompts(cfg, lens, seed):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab, n).astype(np.int32) for n in lens]


def _exact(model, params, prompt):
    """``prompt[:-1]`` through a batch-1 prefill of its own length, then
    the last prompt token decoded: (cache after the prefill, logits)."""
    cache = model.init_cache(batch=1, max_len=MAX_LEN)
    n = len(prompt) - 1
    _, cache = jax.jit(model.prefill)(params, jnp.asarray(prompt[None, :n]),
                                      cache)
    logits, _ = jax.jit(model.decode_step)(
        params, jnp.asarray(prompt[None, n:]), cache,
        jnp.asarray([n], jnp.int32))
    return cache, logits[0, -1]


@pytest.mark.parametrize("lens", [(2, 13, 20), (3, 9, 30, 17, 2)],
                         ids=["3rows-in-4", "5rows-in-8"])
def test_masked_bucketed_prefill_equals_the_exact_prefill(hybrid, lens):
    """Rows of mixed lengths, one of them below ``d_conv - 1``, share one
    right-padded prefill of bucket 32 over a power-of-two row bucket (the
    padding rows at length 0): each row's SSM state, conv state and the
    next decode's logits are the exact prefill's."""
    cfg, model, params = hybrid
    prompts = _prompts(cfg, lens, seed=len(lens))
    rows = row_bucket(len(lens), 8)
    lengths = np.zeros(rows, np.int32)
    toks = np.zeros((rows, 32), np.int32)
    last = np.zeros((rows, 1), np.int32)
    for j, p in enumerate(prompts):
        lengths[j] = len(p) - 1
        toks[j, :len(p) - 1] = p[:-1]
        last[j, 0] = p[-1]
    cache = model.init_cache(batch=rows, max_len=MAX_LEN)
    _, cache = jax.jit(model.prefill)(params, jnp.asarray(toks), cache,
                                      lengths=jnp.asarray(lengths))
    logits, _ = jax.jit(model.decode_step)(params, jnp.asarray(last), cache,
                                           jnp.asarray(lengths))
    for j, p in enumerate(prompts):
        want_cache, want_logits = _exact(model, params, p)
        for name in ("s0_mamba2", "s2_mamba2"):
            for leaf in ("ssm", "conv"):
                got = np.asarray(cache[name][leaf][:, j], np.float32)
                want = np.asarray(want_cache[name][leaf][:, 0], np.float32)
                np.testing.assert_allclose(got, want, rtol=0, atol=1e-5,
                                           err_msg=f"{name}/{leaf} row {j}")
        np.testing.assert_allclose(np.asarray(logits[j, -1]),
                                   np.asarray(want_logits), rtol=0,
                                   atol=1e-5)


def test_a_short_row_keeps_its_own_conv_inputs(hybrid):
    """A row of length 2 holds a zero and its two inputs as conv state,
    not the bucket's last inputs."""
    cfg, model, params = hybrid
    toks = jnp.asarray(_prompts(cfg, (32,), seed=3)[0][None])
    cache = model.init_cache(batch=1, max_len=MAX_LEN)
    _, full = jax.jit(model.prefill)(params, toks, cache)
    _, short = jax.jit(model.prefill)(params, toks, cache,
                                      lengths=jnp.asarray([2], jnp.int32))
    conv = np.asarray(short["s0_mamba2"]["conv"], np.float32)[0, 0]
    assert not conv[0].any() and conv[1:].any()
    assert not np.array_equal(conv, np.asarray(full["s0_mamba2"]["conv"],
                                               np.float32)[0, 0])


@pytest.mark.parametrize("width", [4, 3])
def test_bucketed_engine_serves_the_exact_paths_tokens(hybrid, width):
    """Two waves of requests (the second reusing slots that held state)
    through the bucketed engine serve the same greedy tokens as through
    ``prompt_buckets=None``; a prompt of one token included."""
    cfg, model, params = hybrid
    waves = [_prompts(cfg, (5, 1, 21, 12), seed=width),
             _prompts(cfg, (2, 30, 9), seed=width + 10)]

    def served(buckets):
        eng = ServingEngine(model, params, width=width, max_len=MAX_LEN,
                            prompt_buckets=buckets)
        out = {}
        for w, prompts in enumerate(waves):
            for i, p in enumerate(prompts):
                eng.submit(Request(rid=10 * w + i, prompt=p,
                                   max_new_tokens=4 + i))
            out.update({r.rid: r.tokens for r in eng.run()})
        return eng, out

    eng, got = served("auto")
    assert eng.prompt_buckets is not None      # hybrid: buckets on
    _, want = served(None)
    assert got == want and len(got) == 7


def test_xlstm_keeps_the_exact_path():
    cfg = get_config("xlstm-125m").replace(
        n_layers=2, d_model=32, n_heads=2, n_kv_heads=2, vocab=64)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    assert ServingEngine(model, params, width=2,
                         max_len=32).prompt_buckets is None


@pytest.mark.parametrize("S", [13, 8, 5])
def test_ssd_pads_a_ragged_tail_to_whole_chunks(S):
    """``ssd`` over a length that is no multiple of its chunk gives the
    per-position recurrence's outputs and final state."""
    B, H, P, N = 2, 3, 4, 5
    ks = jax.random.split(jax.random.PRNGKey(S), 4)
    x = jax.random.normal(ks[0], (B, S, H, P))
    log_a = -jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    b = jax.random.normal(ks[2], (B, S, 1, N))
    c = jax.random.normal(ks[3], (B, S, 1, N))
    with jax.default_matmul_precision("highest"):
        y, final = ssd(x, log_a, b, c, chunk=4)
    h = np.zeros((B, H, N, P))
    ys = []
    xn, an, bn, cn = (np.asarray(t, np.float64) for t in (x, log_a, b, c))
    for t in range(S):
        h = (h * np.exp(an[:, t])[:, :, None, None]
             + np.einsum("bn,bhp->bhnp", bn[:, t, 0], xn[:, t]))
        ys.append(np.einsum("bn,bhnp->bhp", cn[:, t, 0], h))
    np.testing.assert_allclose(np.asarray(y), np.stack(ys, 1), atol=1e-4)
    np.testing.assert_allclose(np.asarray(final), h, atol=1e-4)


def test_gated_norm_is_rmsnorm_of_y_times_silu_z():
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    y = jax.random.normal(ks[0], (2, 3, 16))
    z = jax.random.normal(ks[1], (2, 3, 16))
    p = {"scale": 0.1 * jax.random.normal(ks[2], (16,))}
    g = np.asarray(y) * np.asarray(jax.nn.silu(z))
    want = (g / np.sqrt(np.mean(g ** 2, -1, keepdims=True) + 1e-5)
            * (1 + np.asarray(p["scale"])))
    got = np.asarray(gated_rmsnorm(p, y, z, eps=1e-5))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    old = np.asarray(L.rmsnorm(p, y, eps=1e-5) * jax.nn.silu(z))
    assert np.abs(old - want).max() > 1e-2


def _llama(**kw):
    cfg = get_config("granite-8b").replace(
        n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
        d_ff=96, vocab=128, q_chunk=32, remat=False, loss_seq_chunk=None,
        **kw)
    model = build_model(cfg)
    return cfg, model, model.init(jax.random.PRNGKey(1), jnp.float32)


def _outputs(model, params):
    """Prefill logits and two decode steps' logits of a fixed prompt."""
    toks = jnp.asarray(np.arange(3, 23, dtype=np.int32)[None])
    with jax.default_matmul_precision("highest"):
        cache = model.init_cache(batch=1, max_len=32)
        lg, cache = jax.jit(model.prefill)(params, toks, cache)
        outs = [lg]
        step = jax.jit(model.decode_step)
        for i in range(2):
            tok = jnp.argmax(outs[-1][:, -1], -1).astype(jnp.int32)[:, None]
            lg, cache = step(params, tok, cache, jnp.int32(20 + i))
            outs.append(lg)
    return np.concatenate([np.asarray(o, np.float32).ravel() for o in outs])


# sha256 of ``_outputs`` of the tiny llama block at the default
# multipliers, recorded from the program before they existed
PARENT_SHA = "7f5ae674c88e58aa934caab17f244cd8e6d6f9a84325eb6e73b2f9c70488d089"


def test_multipliers_at_their_defaults_give_the_parents_outputs():
    _, model, params = _llama()
    got = hashlib.sha256(_outputs(model, params).tobytes()).hexdigest()
    assert got == PARENT_SHA


def test_granite_multipliers_match_a_hand_scaled_forward():
    """Embeddings x12, each sub-layer's output x0.22 before its residual
    add, logits /8 and every RMSNorm at eps 1e-5."""
    cfg, model, params = _llama(embedding_multiplier=12.0,
                                residual_multiplier=0.22,
                                logits_scaling=8.0, norm_eps=1e-5)
    toks = jnp.asarray(np.arange(5, 25, dtype=np.int32)[None])
    pos = jnp.arange(20)[None]

    def norm(p, x):
        return L.rmsnorm(p, x, eps=1e-5)

    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["embed"]["table"], toks, axis=0) * 12.0
        for g in range(cfg.n_groups):
            pg = jax.tree.map(lambda a: a[g], params["layers"])
            h, _ = L.attention(pg["s0_attn"]["attn"],
                               norm(pg["s0_attn"]["norm"], x),
                               positions=pos, q_chunk=32)
            x = x + 0.22 * h
            h = L.mlp(pg["s1_mlp"]["mlp"], norm(pg["s1_mlp"]["norm"], x),
                      activation=cfg.activation)
            x = x + 0.22 * h
        want = L.unembed(params["embed"], norm(params["final_norm"], x)) / 8
        # no cache: attention over the prompt itself, so no K/V is rounded
        # to the cache's bfloat16
        got, _ = model.prefill(params, toks, None)
        hidden, _ = model.forward(params, toks)
    np.testing.assert_allclose(np.asarray(got[0, -1]),
                               np.asarray(want[0, -1]), atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(L.unembed(params["embed"], hidden) / 8),
        np.asarray(want), atol=1e-5)


def _step_spans(path):
    f = sorted(glob.glob(f"{path}/**/*.xplane.pb", recursive=True))[-1]
    out = []
    for plane in ProfileData.from_file(f).planes:
        for line in plane.lines:
            out += [(e.start_ns, e.name, dict(e.stats)) for e in line.events
                    if e.name in ("serving.step", "serving.scatter",
                                  "serving.decode")]
    return [(n, s) for _, n, s in sorted(out, key=lambda t: t[0])]


def test_step_counters_are_the_pools_bytes(hybrid, tmp_path):
    """``kv_used``, ``state_used`` and ``reserved`` on ``serving.step``
    and ``state_bytes`` on ``serving.scatter`` equal the pool's bytes
    counted by hand from the configuration's widths."""
    cfg, model, params = hybrid
    width = 4
    H = cfg.ssm_expand * cfg.d_model // cfg.ssm_head_dim
    conv_dim = cfg.ssm_expand * cfg.d_model + 2 * cfg.ssm_state
    # two Mamba-2 layers: f32 SSM state and bf16 conv state a slot; one
    # attention layer: bf16 K and V a position
    state = 2 * (4 * H * cfg.ssm_state * cfg.ssm_head_dim
                 + 2 * (cfg.ssm_conv - 1) * conv_dim)
    position = 2 * 2 * cfg.n_kv_heads * cfg.head_dim
    slot = state + MAX_LEN * position
    eng = ServingEngine(model, params, width=width, max_len=MAX_LEN)
    assert (eng.pool.state_bytes, eng.pool.position_bytes) == \
        (state, position)
    assert eng.pool.reserved_bytes == width * slot
    prompts = _prompts(cfg, (6, 11, 3), seed=8)
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=p, max_new_tokens=3 + i))
    eng.warmup()
    jax.profiler.start_trace(str(tmp_path))
    try:
        while eng.queue or eng.active:
            eng.step()
    finally:
        jax.profiler.stop_trace()
    spans = _step_spans(str(tmp_path))
    steps = [s for n, s in spans if n == "serving.step"]
    (scatter,) = [s for n, s in spans if n == "serving.scatter"]
    assert scatter["state_bytes"] == 3 * slot
    assert all(s["reserved"] == width * slot for s in steps)
    # the first tick's decode runs over the three prompts less their last
    # tokens; each later tick's over one more position a row still active
    filled = [len(p) - 1 for p in prompts]
    active = [0, 1, 2]
    for s in steps:
        assert s["kv_used"] == position * sum(filled[i] for i in active)
        assert s["state_used"] == state * len(active)
        for i in list(active):
            filled[i] += 1
            if filled[i] - (len(prompts[i]) - 1) == 3 + i:
                active.remove(i)
    assert not active
