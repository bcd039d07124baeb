"""Work that one mixture-of-experts sub-layer needs: the router over the
real experts, 2 * D * E FLOPs per token, and top-k experts of three
D x F matrices each, 6 * D * F * k FLOPs per token.  Bytes: the router and
every real expert (the published count, not the program's padded one).
A step in which some expert gets no token needs less than this; with n
tokens routed uniformly the share of experts left unrouted is
(1 - k/E)^n, which is the over-count of ``weight_bytes`` for that step."""

from __future__ import annotations

ELEM = 2


def flops(model: dict, tokens: int, pairs: int) -> float:
    D, F = model["d_model"], model["d_ff"]
    E, k = model["moe_experts"], model["moe_top_k"]
    return (2.0 * D * E + 6.0 * D * F * k) * tokens


def weight_bytes(model: dict) -> int:
    D, F, E = model["d_model"], model["d_ff"], model["moe_experts"]
    return ELEM * (D * E + 3 * D * F * E + D)


def state_bytes(model: dict, positions: int) -> int:
    return 0
