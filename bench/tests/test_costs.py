import json
from pathlib import Path

import jax
import pytest

import costs
from cost import attn, embed, mlp, moe, unembed

CONFIGS = Path(__file__).parents[1] / "configs"
M = {"d_model": 8, "head_dim": 2, "n_heads": 4, "n_kv_heads": 2,
     "d_ff": 6, "vocab": 10, "moe_experts": 5, "moe_top_k": 2,
     "tie_word_embeddings": True, "pattern": ["attn", "moe"],
     "n_layers": 3}


def test_attention_by_hand():
    # q: 8x4x2, k and v: 8x2x2 each, o: 4x2x8 -> 8*2*12 = 192 weights;
    # 2 FLOPs each per token, plus 4*H*hd = 32 per visited pair
    assert attn.flops(M, 1, 0) == 2 * 192
    assert attn.flops(M, 3, 6) == 2 * 192 * 3 + 32 * 6
    assert attn.weight_bytes(M) == 2 * (192 + 8)
    assert attn.state_bytes(M, 5) == 2 * 2 * 2 * 2 * 5


def test_mlp_moe_embed_unembed_by_hand():
    assert mlp.flops(M, 2, 99) == 2 * 2 * 3 * 8 * 6
    assert mlp.weight_bytes(M) == 2 * (3 * 8 * 6 + 8)
    # router 8x5 and two experts of three 8x6 matrices per token
    assert moe.flops(M, 1, 0) == 2 * 8 * 5 + 2 * 2 * 3 * 8 * 6
    assert moe.weight_bytes(M) == 2 * (8 * 5 + 5 * 3 * 8 * 6 + 8)
    assert embed.flops(M, 4, 0) == 0 and embed.weight_bytes(M) == 0
    assert embed.token_bytes(M, 3) == 2 * 8 * 3
    assert unembed.flops(M, 2, 0) == 2 * 2 * 10 * 8
    assert unembed.weight_bytes(M) == 2 * (10 * 8 + 8)


def test_step_sums():
    per_layer = attn.flops(M, 4, 10) + moe.flops(M, 4, 10)
    assert costs.prefill_flops(M, 4) == 3 * per_layer
    dec = 3 * (attn.flops(M, 2, 3 + 6) + moe.flops(M, 2, 0)) \
        + unembed.flops(M, 2, 0)
    assert costs.decode_flops(M, [2, 5]) == dec
    assert costs.decode_bytes(M, [2, 5]) == costs.weight_bytes(M) + \
        3 * attn.state_bytes(M, 2 + 5 + 2) + embed.token_bytes(M, 2)


def test_a_kind_without_a_counter_is_an_error():
    with pytest.raises(SystemExit, match="mamba2"):
        costs.prefill_flops(dict(M, pattern=["attn", "mamba2"]), 4)


@pytest.mark.parametrize("name", ["granite-moe-3b-a800m", "granite-8b-half"])
def test_weight_bytes_match_the_program_less_padding(name):
    from repro.models import build_model, get_config
    conf = json.loads((CONFIGS / f"{name}.json").read_text())
    model = conf["model"]
    cfg = get_config(conf["arch"]).replace(**conf["overrides"])
    tree = jax.eval_shape(build_model(cfg).init, jax.random.PRNGKey(0))
    n = sum(x.size for x in jax.tree.leaves(tree))
    D, F, L = model["d_model"], model["d_ff"], model["n_layers"]
    pad = (model["vocab_padded"] - model["vocab"]) * D
    if model["moe_experts"]:
        extra = model["moe_experts_padded"] - model["moe_experts"]
        pad += L * extra * (3 * D * F + D)
    assert costs.weight_bytes(model) == 2 * (n - pad)
