"""Token embedding: a row lookup, no FLOPs; D elements read per token.
With tied embeddings the table itself is counted by ``unembed``."""

from __future__ import annotations

ELEM = 2


def flops(model: dict, tokens: int, pairs: int) -> float:
    return 0.0


def weight_bytes(model: dict) -> int:
    return 0 if model["tie_word_embeddings"] else \
        ELEM * model["vocab"] * model["d_model"]


def token_bytes(model: dict, tokens: int) -> int:
    return ELEM * model["d_model"] * tokens


def state_bytes(model: dict, cache_lens: list[int]) -> int:
    return 0
