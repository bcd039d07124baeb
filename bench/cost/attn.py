"""Work that one attention sub-layer needs, from its shapes.

FLOPs: the Q, K, V and output projections, 2 * D * hd * (2H + 2Hk) per
token, and the scores and the weighted sum of values, 4 * H * hd per
(query, key) pair that causal attention visits.  Bytes: the four
projection matrices, and 2 * Hk * hd elements of K and V per position:
in a decode step each row reads its filled positions and writes one."""

from __future__ import annotations

ELEM = 2  # bytes of a bfloat16 element


def flops(model: dict, tokens: int, pairs: int) -> float:
    D, hd = model["d_model"], model["head_dim"]
    H, Hk = model["n_heads"], model["n_kv_heads"]
    return 2.0 * D * hd * (2 * H + 2 * Hk) * tokens + 4.0 * H * hd * pairs


def weight_bytes(model: dict) -> int:
    D, hd = model["d_model"], model["head_dim"]
    H, Hk = model["n_heads"], model["n_kv_heads"]
    return ELEM * (D * hd * (2 * H + 2 * Hk) + D)


def state_bytes(model: dict, cache_lens: list[int]) -> int:
    positions = sum(c + 1 for c in cache_lens)
    return ELEM * 2 * model["n_kv_heads"] * model["head_dim"] * positions
