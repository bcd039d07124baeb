"""Final norm and the unembedding over the real vocabulary: 2 * V * D
FLOPs per token whose logits are needed, and the V x D table's bytes once
per step."""

from __future__ import annotations

ELEM = 2


def flops(model: dict, tokens: int, pairs: int) -> float:
    return 2.0 * model["vocab"] * model["d_model"] * tokens


def weight_bytes(model: dict) -> int:
    return ELEM * (model["vocab"] * model["d_model"] + model["d_model"])


def state_bytes(model: dict, cache_lens: list[int]) -> int:
    return 0
