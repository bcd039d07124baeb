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
    # a row holding 4 positions reads them and writes a fifth
    assert attn.state_bytes(M, [4]) == 2 * 2 * 2 * 2 * 5
    assert attn.state_bytes(M, [4, 0]) == 2 * 2 * 2 * 2 * (5 + 1)


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
        3 * attn.state_bytes(M, [2, 5]) + embed.token_bytes(M, 2)


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


def test_expert_share_and_shared_expert_by_hand():
    # 5 of 10 published experts held here: the router over all 10, half
    # the expected top-2 expert work; a gated shared expert of width 4
    S = dict(M, moe_router_experts=10, moe_shared_d_ff=4)
    assert moe.flops(S, 1, 0) == 2 * 8 * 10 + 2 * 2 * 3 * 8 * 6 * 5 / 10 \
        + 2 * 3 * 8 * 4 + 2 * 8
    assert moe.weight_bytes(S) == 2 * (8 * 10 + 5 * 3 * 8 * 6 + 8
                                       + 3 * 8 * 4 + 8)
    # an ungated shared expert has no gate column
    U = dict(S, moe_shared_gate=None)
    assert moe.flops(U, 1, 0) == moe.flops(S, 1, 0) - 2 * 8
    assert moe.weight_bytes(U) == moe.weight_bytes(S) - 2 * 8


def test_a_group_of_two_layers_is_counted_once_a_group():
    # 6 layers in 3 groups of (attn, moe, attn, moe)
    G = dict(M, pattern=["attn", "moe", "attn", "moe"], n_layers=6,
             n_groups=3)
    per_group = 2 * (attn.flops(M, 4, 10) + moe.flops(M, 4, 10))
    assert costs.prefill_flops(G, 4) == 3 * per_group
    assert costs.weight_bytes(G) == 3 * 2 * (attn.weight_bytes(M)
                                             + moe.weight_bytes(M)) \
        + unembed.weight_bytes(M)
    assert costs.decode_bytes(G, [2, 5]) == costs.weight_bytes(G) + \
        3 * 2 * attn.state_bytes(M, [2, 5]) + embed.token_bytes(M, 2)


# recorded from the cost functions before groups, expert shares and
# shared experts were counted: the defaults leave every number as it was
RECORDED = {
    "granite-moe-3b-a800m": {
        "prefill": [1614741504.0, 418126233600.0, 1296262496256.0],
        "decode_flops": [5557152768.0, 1766728704.0],
        "weight_bytes": 6597586944,
        "decode_bytes": [6684431360, 6597983232]},
    "granite-8b-half": {
        "prefill": [7852032000.0, 2011818885120.0, 6109142188032.0],
        "decode_flops": [25153929216.0, 8256159744.0],
        "weight_bytes": 8254693376,
        "decode_bytes": [8352407552, 8255143936]},
}


@pytest.mark.parametrize("name", sorted(RECORDED))
def test_accepted_configurations_cost_as_recorded(name):
    model = json.loads((CONFIGS / f"{name}.json").read_text())["model"]
    want = RECORDED[name]
    assert [costs.prefill_flops(model, n) for n in (1, 255, 767)] == \
        want["prefill"]
    assert [costs.decode_flops(model, c) for c in ([0, 300, 1022], [5])] \
        == want["decode_flops"]
    assert costs.weight_bytes(model) == want["weight_bytes"]
    assert [costs.decode_bytes(model, c) for c in ([0, 300, 1022], [5])] \
        == want["decode_bytes"]
