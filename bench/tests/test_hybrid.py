"""The Mamba-2 hybrid in the harness: ``reference/mamba2.py`` against the
program's bucketed prefill and cached decode, ``check_config`` on the
Mamba-2 keys, ``cost/mamba2.py`` by hand, and ``pool_used_share`` on
spans of known counters; with a tiny hybrid configuration of this file's
own."""

import json
import time
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import costs
import peaks
import run
import spans
import weights
from cost import mamba2 as mamba2_cost
from drivers.lm_serving import check_config
from metrics import pool_used_share
from reference.lm import logits

CONFIGS = Path(__file__).parents[1] / "configs"

# two Mamba-2 layers and one NoPE attention layer a group, each followed by
# a gated MLP, with Granite's multipliers
PATTERN = ["mamba2", "mlp", "mamba2", "mlp", "attn", "mlp"]
MODEL = {
    "n_layers": 6, "n_groups": 2, "d_model": 64, "n_heads": 4,
    "n_kv_heads": 2, "head_dim": 16, "d_ff": 96, "vocab": 250,
    "vocab_padded": 256, "pattern": PATTERN, "moe_experts": 0,
    "moe_experts_padded": 0, "moe_top_k": 0, "rope": False,
    "rope_theta": 10000.0, "attention_multiplier": 0.015625,
    "embedding_multiplier": 12.0, "residual_multiplier": 0.22,
    "logits_scaling": 8.0, "ssm_state": 8, "ssm_conv": 4, "ssm_expand": 2,
    "ssm_head_dim": 8, "ssm_chunk": 8, "norm_eps": 1e-05,
    "activation": "silu", "tie_word_embeddings": True, "dtype": "float32",
}

CONF = {
    "name": "tiny-hybrid", "driver": "lm_serving",
    "arch": "granite-4.0-h-micro",
    "overrides": {"n_layers": 6,
                  "pattern": (("mamba2", "mlp"), ("mamba2", "mlp"),
                              ("attn", "mlp")),
                  "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
                  "head_dim": 16, "d_ff": 96, "vocab": 256, "ssm_state": 8,
                  "ssm_head_dim": 8, "ssm_chunk": 8},
    "width": 4, "max_len": 128, "model": MODEL,
    "limits": {"token_gap": 0.05},
}

MIX = {
    "generator": "poisson", "rate_rps": 6.0, "lead_in_s": 1,
    "prompt": {"dist": "lognormal", "median": 24, "sigma": 0.5, "min": 8,
               "max": 60},
    "output": {"dist": "uniform", "min": 4, "max": 12},
    "check_tokens": 48,
}


def program(conf: dict, **over):
    from repro.models import get_config
    return get_config(conf["arch"]).replace(**dict(conf["overrides"],
                                                   **over))


def f32_weights(seed=3):
    from repro.models import build_model
    m = build_model(program(CONF))
    tmpl = jax.eval_shape(lambda k: m.init(k, jnp.float32),
                          jax.random.PRNGKey(0))
    return m, weights.make(tmpl, MODEL, jax.random.PRNGKey(seed))


def test_prefill_then_cached_decode_match_the_reference_in_f32():
    """Two prompts of different lengths share one right-padded prefill
    with their lengths, then decode six fixed tokens each through the
    cache (held in float32 here, so that only the arithmetic differs):
    every decode's logits are the reference's at that position."""
    m, w = f32_weights()
    rng = np.random.default_rng(5)
    seqs = [rng.integers(0, MODEL["vocab"], n).astype(np.int32)
            for n in (19, 30)]
    plen = [len(s) - 6 for s in seqs]
    toks = np.zeros((2, 32), np.int32)
    for j, s in enumerate(seqs):
        toks[j, :plen[j] - 1] = s[:plen[j] - 1]
    lens = np.asarray([n - 1 for n in plen], np.int32)
    cache = jax.tree.map(lambda c: c.astype(jnp.float32),
                         m.init_cache(batch=2, max_len=64))
    with jax.default_matmul_precision("highest"):
        _, cache = jax.jit(m.prefill)(w, jnp.asarray(toks), cache,
                                      lengths=jnp.asarray(lens))
        step = jax.jit(m.decode_step)
        got = []
        for t in range(6):
            tok = np.asarray([[s[n - 1 + t]] for s, n in zip(seqs, plen)],
                             np.int32)
            lg, cache = step(w, jnp.asarray(tok), cache,
                             jnp.asarray(lens + t))
            got.append(np.asarray(lg[:, 0]))
    for j, s in enumerate(seqs):
        ref = np.asarray(logits(MODEL, w, jnp.asarray(s), "f32"))
        want = ref[plen[j] - 1:plen[j] + 5]
        np.testing.assert_allclose(np.stack([g[j] for g in got]), want,
                                   rtol=0, atol=1e-4)


def test_the_configuration_file_passes_and_costs_the_programs_weights():
    from repro.models import build_model
    conf = json.loads((CONFIGS / "granite-4.0-h-micro.json").read_text())
    cfg = program(conf)
    check_config(cfg, conf["model"])
    assert conf["reduced"] == [] and conf["num_hidden_layers"] == 40
    tree = jax.eval_shape(build_model(cfg).init, jax.random.PRNGKey(0))
    n = sum(x.size for x in jax.tree.leaves(tree))
    assert costs.weight_bytes(conf["model"]) == 2 * n


def test_the_tiny_hybrid_passes():
    check_config(program(CONF), MODEL)


@pytest.mark.parametrize("key,value,field", [
    ("ssm_state", 16, "ssm_state: file 16, program 8"),
    ("norm_eps", 1e-06, "norm_eps: file 1e-06, program 1e-05"),
    ("residual_multiplier", 1.0,
     "residual_multiplier: file 1.0, program 0.22")])
def test_a_disagreeing_mamba2_key_is_refused(key, value, field):
    with pytest.raises(SystemExit) as e:
        check_config(program(CONF), dict(MODEL, **{key: value}))
    assert field in str(e.value)


def test_a_mamba2_key_left_out_is_refused():
    model = {k: v for k, v in MODEL.items() if k != "ssm_head_dim"}
    with pytest.raises(SystemExit, match="ssm_head_dim: not in the file"):
        check_config(program(CONF), model)


def test_mamba2_cost_by_hand():
    # D 8, d_inner 16, heads of 4 -> H 4, N 3, K 4, conv channels 22
    M = {"d_model": 8, "ssm_expand": 2, "ssm_head_dim": 4, "ssm_state": 3,
         "ssm_conv": 4}
    proj_in = 8 * (2 * 16 + 2 * 3 + 4)            # 336
    assert mamba2_cost.flops(M, 1, 99) == (2 * proj_in + 2 * 4 * 22
                                           + 5 * 4 * 3 * 4 + 2 * 16 * 8)
    assert mamba2_cost.flops(M, 3, 0) == 3 * mamba2_cost.flops(M, 1, 0)
    assert mamba2_cost.weight_bytes(M) == 2 * (
        proj_in + 4 * 22 + 22 + 3 * 4 + 16 + 16 * 8 + 8)
    # per row, whatever its length: the f32 SSM state and the bf16 conv
    # state, each read and written
    row = 2 * 4 * 4 * 3 * 4 + 2 * 2 * 3 * 22
    assert mamba2_cost.state_bytes(M, [5]) == row
    assert mamba2_cost.state_bytes(M, [0, 700, 3]) == 3 * row


def test_hybrid_step_sums_count_each_kind_a_group():
    dec = costs.decode_bytes(MODEL, [4, 9])
    assert dec == (costs.weight_bytes(MODEL)
                   + 2 * (2 * mamba2_cost.state_bytes(MODEL, [4, 9])
                          + costs.kind("attn").state_bytes(MODEL, [4, 9]))
                   + costs.kind("embed").token_bytes(MODEL, 2))


def _ctx(span_list):
    return SimpleNamespace(spans=span_list)


def _step(t0, kv, st, res, decode=True):
    out = [spans.Span("serving.step", t0, t0 + 0.01,
                      {"queued": 0, "active": 1, "kv_used": kv,
                       "state_used": st, "reserved": res})]
    if decode:
        out.append(spans.Span("serving.decode", t0 + 0.001, t0 + 0.002,
                              {"rows": 1}))
    return out


def test_pool_used_share_of_known_steps():
    got = _step(0.0, 100, 300, 1000) + _step(0.02, 50, 50, 1000) \
        + _step(0.04, 999, 999, 1000, decode=False)
    # only the steps that hold a decode: (400 + 100) / 2000
    assert pool_used_share.read(_ctx(got)) == pytest.approx(25.0)


def test_pool_used_share_reads_nothing_without_counters():
    bare = [spans.Span("serving.step", 0.0, 0.01, {"queued": 0,
                                                   "active": 1}),
            spans.Span("serving.decode", 0.001, 0.002, {"rows": 1})]
    assert pool_used_share.read(_ctx(bare)) is None
    assert pool_used_share.read(_ctx([])) is None


def test_traced_run_of_the_tiny_hybrid_on_the_cpu(monkeypatch):
    """The driver serves the tiny hybrid through the bucketed path, the
    reference agrees, nothing compiles in the window, and the traced run
    reports ``pool_used_share`` beside the decode metrics."""
    monkeypatch.setattr(peaks, "lookup",
                        lambda kind: peaks.TABLE["devices"]["TPU v5 lite"])
    spec = {"end_to_end": [], "per_layer": [
        {"name": n, "unit": "%"} for n in
        ("pool_used_share", "decode_roofline", "device_idle")]}
    res = run.execute(spec, "tiny-hybrid", CONF, MIX, 2**33 + 5, 2.0, True,
                      jax.devices()[:1], time.perf_counter())
    assert res["correct"], res["checks"]
    assert res["info"]["compiles_in_window"] == 0
    share = res["metrics"]["pool_used_share"]["value"]
    assert 0.0 < share < 100.0
