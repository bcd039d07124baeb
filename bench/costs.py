"""FLOPs and bytes that a serving step needs, summed from the per-kind
counters in ``cost/<kind>.py`` (one file per sub-layer kind of the
configuration's ``pattern``, plus ``embed`` and ``unembed``; a kind that
has no file is an error).  These count what the algorithm needs, not what
the program does: no padding rows or positions, no masked cache reads, no
copies."""

from __future__ import annotations

import importlib


def kind(name: str):
    try:
        return importlib.import_module(f"cost.{name}")
    except ModuleNotFoundError as e:
        raise SystemExit(f"no cost counter for kind {name!r}: add "
                         f"bench/cost/{name}.py") from e


def stack(model: dict):
    """(kind module, times) for every sub-layer of the stack: each kind of
    the pattern, which is one group, once a group."""
    n = model.get("n_groups", model["n_layers"])
    return [(kind(k), n) for k in model["pattern"]]


def prefill_flops(model: dict, tokens: int) -> float:
    """A causal prefill of ``tokens`` real tokens through every layer (the
    engine takes the first generated token from decode, so no logits)."""
    pairs = tokens * (tokens + 1) // 2
    return sum(m.flops(model, tokens, pairs) * n for m, n in stack(model))


def decode_flops(model: dict, cache_lens: list[int]) -> float:
    """One decode step over the active rows, row r attending to
    ``cache_lens[r] + 1`` positions, with logits for each row."""
    t = len(cache_lens)
    pairs = sum(c + 1 for c in cache_lens)
    return (sum(m.flops(model, t, pairs) * n for m, n in stack(model))
            + kind("unembed").flops(model, t, 0))


def weight_bytes(model: dict) -> int:
    return (sum(m.weight_bytes(model) * n for m, n in stack(model))
            + kind("embed").weight_bytes(model)
            + kind("unembed").weight_bytes(model))


def decode_bytes(model: dict, cache_lens: list[int]) -> int:
    """One decode step: every weight once, each kind's state for the
    active rows, whose caches hold ``cache_lens`` positions (for attention
    the K/V of the filled positions read and one position written a row),
    and the embedding rows of the input tokens."""
    t = len(cache_lens)
    state = sum(m.state_bytes(model, cache_lens) * n
                for m, n in stack(model))
    return (weight_bytes(model) + state
            + kind("embed").token_bytes(model, t))
