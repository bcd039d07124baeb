import hashlib
import json
import math
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tiny
import weights
from reference import attn, mlp, moe
from reference.numerics import rmsnorm
from reference.lm import hidden, logits, token_gaps

CONFIGS = Path(__file__).parents[1] / "configs"


def small(conf: dict, layers: int = 2) -> dict:
    conf = json.loads(json.dumps(conf))
    conf["overrides"]["n_layers"] = layers
    conf["model"]["n_layers"] = layers
    return conf


def program_logits(conf, w, toks):
    from repro.models import build_model, get_config
    from repro.models import layers as L
    m = build_model(get_config(conf["arch"]).replace(**conf["overrides"]))
    with jax.default_matmul_precision("highest"):
        h, _ = m.forward(w, jnp.asarray(toks)[None])
        return L.unembed(w["embed"], h)[0]


def f32_weights(conf, seed=3):
    from repro.models import build_model, get_config
    model = dict(conf["model"], dtype="float32")
    m = build_model(get_config(conf["arch"]).replace(**conf["overrides"]))
    tmpl = jax.eval_shape(lambda k: m.init(k, jnp.float32),
                          jax.random.PRNGKey(0))
    return model, weights.make(tmpl, model, jax.random.PRNGKey(seed))


@pytest.mark.parametrize("conf", [
    tiny.CONF,
    small(json.loads((CONFIGS / "granite-moe-3b-a800m.json").read_text())),
    small(json.loads((CONFIGS / "granite-8b-half.json").read_text())),
    tiny.VARIANT_CONF],
    ids=["tiny", "granite-moe", "granite-8b", "variant"])
def test_reference_equals_the_program_forward_in_f32(conf):
    """The plain reference and the program's full-sequence forward give
    the same logits on the same weights (float32, highest precision):
    the reference states the program's model."""
    model, w = f32_weights(conf)
    toks = np.random.default_rng(0).integers(0, model["vocab"], 40)
    ref = logits(model, w, jnp.asarray(toks, jnp.int32), "f32")
    got = program_logits(conf, w, toks)
    assert float(jnp.max(jnp.abs(ref - got))) < 1e-4


def test_weights_zero_the_padding_and_follow_the_seed():
    model, w = f32_weights(tiny.CONF, seed=5)
    _, w2 = f32_weights(tiny.CONF, seed=5)
    _, w3 = f32_weights(tiny.CONF, seed=6)
    t = np.asarray(w["embed"]["table"])
    assert not t[model["vocab"]:].any() and t[:model["vocab"]].std() > 0
    ex = np.asarray(w["layers"]["s1_moe"]["moe"]["w_up"])
    assert not ex[:, model["moe_experts"]:].any()
    assert np.array_equal(t, np.asarray(w2["embed"]["table"]))
    assert not np.array_equal(t, np.asarray(w3["embed"]["table"]))


def test_gaps_of_the_reference_itself_are_zero():
    model, w = f32_weights(tiny.CONF)
    prompt = np.arange(1, 20, dtype=np.int32)
    seq = list(prompt)
    for _ in range(6):       # greedy continuation by the reference
        lg = logits(model, w, jnp.asarray(seq, jnp.int32), "f32")
        seq.append(int(jnp.argmax(lg[-1])))
    served = np.asarray(seq[len(prompt):], np.int32)
    gap, low = token_gaps(model, w, prompt, served, 64, control=True)
    assert gap.shape == (6,) and float(gap.max()) == 0.0
    assert low.shape == (6,)


def test_variant_weights_draw_the_shared_expert_whole():
    model, w = f32_weights(tiny.VARIANT_CONF)
    m = w["layers"]["s1_moe"]["moe"]
    assert not np.asarray(m["w_up"])[:, model["moe_experts"]:].any()
    assert not np.asarray(m["router"])[:, :, model["moe_experts"]:].any()
    for leaf in (m["shared"]["w_gate"], m["shared"]["w_up"],
                 m["shared"]["w_down"], m["shared_gate"]):
        assert np.asarray(leaf).all()


def _moe_weights(key, D, F, Fs, E):
    k = jax.random.split(key, 6)
    n = jax.random.normal
    return {"router": n(k[0], (D, E)), "w_gate": n(k[1], (E, D, F)),
            "w_up": n(k[2], (E, D, F)), "w_down": n(k[3], (E, F, D)) / F,
            "shared": {"w_gate": n(k[4], (D, Fs)), "w_up": n(k[5], (D, Fs)),
                       "w_down": n(k[0], (Fs, D)) / Fs},
            "shared_gate": n(k[1], (D, 1)) / D}


@pytest.mark.parametrize("gate", ["sigmoid", None])
def test_expert_shares_add_up_to_the_uncut_layer(gate):
    """16 routed experts cut into two shares of 8: each share routes over
    all 16 and computes the experts it holds; the routed parts of both
    shares and the shared expert, counted once, are the uncut layer."""
    D, F, Fs = 32, 16, 24
    model = dict(tiny.MODEL, d_model=D, d_ff=F, moe_experts=16,
                 moe_router_experts=16, moe_top_k=4, moe_shared_d_ff=Fs,
                 moe_shared_gate=gate)
    m = _moe_weights(jax.random.PRNGKey(1), D, F, Fs, 16)
    p = {"norm": {"scale": jnp.zeros(D)}, "moe": m}
    x = jax.random.normal(jax.random.PRNGKey(2), (12, D))
    whole = moe.apply(model, p, x, "f32")
    cut = dict(model, moe_experts=8)
    h = rmsnorm(p["norm"]["scale"], x, model["norm_eps"])
    # the second share holds experts 8-15: list them first
    order = np.r_[8:16, 0:8]
    second = dict(m, router=m["router"][:, order],
                  **{k: m[k][order] for k in ("w_gate", "w_up", "w_down")})
    parts = moe.routed(cut, m, h, "f32") + moe.routed(cut, second, h, "f32")
    assert float(jnp.max(jnp.abs(whole - parts - moe.shared(cut, m, h,
                                                            "f32")))) < 1e-5
    # each share alone is not the layer
    assert float(jnp.max(jnp.abs(moe.routed(cut, m, h, "f32")
                                 - moe.routed(model, m, h, "f32")))) > 1e-2


# sha256 of the float32 and float8 logits of the tiny configuration (seed
# 3, tokens of rng 0), recorded before the multipliers were in the
# reference: with none stated the reference computes what it did, bit for
# bit
TODAY = {"f32": "a869f1e339e0e9ec", "fp8": "bc74b81adb8dd77d"}


@pytest.mark.parametrize("mode", ["f32", "fp8"])
def test_default_multipliers_give_the_recorded_logits(mode):
    model, w = f32_weights(tiny.CONF)
    toks = jnp.asarray(np.random.default_rng(0).integers(0, model["vocab"],
                                                         40), jnp.int32)
    got = np.asarray(logits(model, w, toks, mode))
    assert hashlib.sha256(got.tobytes()).hexdigest()[:16] == TODAY[mode]
    unit = dict(model, embedding_multiplier=1.0, residual_multiplier=1.0,
                logits_scaling=1.0,
                attention_multiplier=1 / math.sqrt(model["head_dim"]))
    assert np.array_equal(np.asarray(logits(unit, w, toks, mode)), got)


def _rms(x, eps=1e-6):
    return x / np.sqrt(np.mean(x * x, -1, keepdims=True) + eps)


def test_each_multiplier_by_hand():
    model, w = f32_weights(tiny.CONF)
    toks = jnp.asarray([3, 17, 5, 200, 9], jnp.int32)
    table = np.asarray(w["embed"]["table"], np.float64)
    e = table[np.asarray(toks)]
    # logits divided by logits_scaling
    base = np.asarray(logits(model, w, toks, "f32"))
    got = np.asarray(logits(dict(model, logits_scaling=16.0), w, toks,
                            "f32"))
    assert np.array_equal(got, base / 16)
    # embeddings times embedding_multiplier, seen through a stack of no
    # groups: the final norm of 12 e (the norm's scale is 0: (1 + 0) x)
    bare = dict(w, layers=jax.tree.map(lambda a: a[:0], w["layers"]))
    got = np.asarray(hidden(dict(model, embedding_multiplier=12.0), bare,
                            toks, "f32"))
    assert np.allclose(got, _rms(12 * e), atol=1e-5)
    assert not np.allclose(got, _rms(e), atol=1e-5)
    # each sub-layer's output times residual_multiplier before the add:
    # one group of one MLP
    mlp_model = dict(tiny.DENSE_MODEL, pattern=["mlp"], n_layers=1)
    mw = {"embed": w["embed"], "final_norm": w["final_norm"],
          "layers": {"s0_mlp": {
              "norm": {"scale": jnp.zeros((1, 64))},
              "mlp": {k: jax.random.normal(jax.random.PRNGKey(i), s) / 8
                      for i, (k, s) in enumerate([
                          ("w_gate", (1, 64, 128)), ("w_up", (1, 64, 128)),
                          ("w_down", (1, 128, 64))])}}}}
    sub = jax.tree.map(lambda a: a[0], mw["layers"]["s0_mlp"])
    out = np.asarray(mlp.apply(mlp_model, sub, jnp.asarray(e, jnp.float32),
                               "f32"), np.float64)
    got = np.asarray(hidden(dict(mlp_model, residual_multiplier=0.22), mw,
                            toks, "f32"))
    assert np.allclose(got, _rms(e + 0.22 * out), atol=1e-5)
    # scores times attention_multiplier: at 0 every query weighs the
    # positions up to its own alike, so each output is the running mean of
    # the values, projected
    a = jax.tree.map(lambda t: t[0], w["layers"]["s0_attn"])
    x = jnp.asarray(e, jnp.float32)
    got = np.asarray(attn.apply(dict(model, attention_multiplier=0.0), a, x,
                                "f32"))
    h = _rms(e)
    v = np.einsum("sd,dhk->shk", h, np.asarray(a["attn"]["wv"], np.float64))
    mean = np.cumsum(v, 0) / np.arange(1, 6)[:, None, None]
    mean = np.repeat(mean, model["n_heads"] // model["n_kv_heads"], axis=1)
    want = np.einsum("shk,hkd->sd", mean,
                     np.asarray(a["attn"]["wo"], np.float64))
    assert np.allclose(got, want, atol=1e-5)
    # and the stated 1/sqrt(head_dim) is the default
    assert np.array_equal(np.asarray(attn.apply(
        dict(model, attention_multiplier=0.25), a, x, "f32")),
        np.asarray(attn.apply(model, a, x, "f32")))
