"""Work that one gated MLP sub-layer needs: three D x F matrices, so
6 * D * F FLOPs per token and their bytes once per step."""

from __future__ import annotations

ELEM = 2


def flops(model: dict, tokens: int, pairs: int) -> float:
    return 6.0 * model["d_model"] * model["d_ff"] * tokens


def weight_bytes(model: dict) -> int:
    return ELEM * (3 * model["d_model"] * model["d_ff"] + model["d_model"])


def state_bytes(model: dict, cache_lens: list[int]) -> int:
    return 0
