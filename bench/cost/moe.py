"""Work that one mixture-of-experts sub-layer needs on this chip.

FLOPs per token: the router over all R = ``moe_router_experts`` published
experts, 2 * D * R; the top-k experts of three D x F matrices each, of
which this chip holds E = ``moe_experts``, so 6 * D * F * k * E / R, the
expected share routed here (all of it where E = R); and a shared expert
of width Fs (``moe_shared_d_ff``), 6 * D * Fs, with 2 * D for its sigmoid
gate.  Bytes: the router, every expert held (the published count, not the
program's padded one), the shared expert and its gate.  A step in which
some expert gets no token needs less than this; with n tokens routed
uniformly the share of experts left unrouted is (1 - k/R)^n, which is the
over-count of ``weight_bytes`` for that step."""

from __future__ import annotations

ELEM = 2


def _shared(model: dict) -> tuple[int, int]:
    """(width, gate columns) of the shared expert."""
    fs = model.get("moe_shared_d_ff", 0)
    gated = fs and model.get("moe_shared_gate", "sigmoid") is not None
    return fs, 1 if gated else 0


def flops(model: dict, tokens: int, pairs: int) -> float:
    D, F = model["d_model"], model["d_ff"]
    E, k = model["moe_experts"], model["moe_top_k"]
    R = model.get("moe_router_experts", E)
    fs, gate = _shared(model)
    return (2.0 * D * R + 6.0 * D * F * k * (E / R)
            + 6.0 * D * fs + 2.0 * D * gate) * tokens


def weight_bytes(model: dict) -> int:
    D, F, E = model["d_model"], model["d_ff"], model["moe_experts"]
    R = model.get("moe_router_experts", E)
    fs, gate = _shared(model)
    return ELEM * (D * R + 3 * D * F * E + D + 3 * D * fs + D * gate)


def state_bytes(model: dict, cache_lens: list[int]) -> int:
    return 0
