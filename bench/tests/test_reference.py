import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tiny
import weights
from reference.lm import logits, token_gaps

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
    small(json.loads((CONFIGS / "granite-8b-half.json").read_text()))],
    ids=["tiny", "granite-moe", "granite-8b"])
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
