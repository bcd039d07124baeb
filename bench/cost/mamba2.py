"""Work that one Mamba-2 mixer needs, from its shapes (di = ``ssm_expand``
x D, H = di / ``ssm_head_dim`` heads of P, N = ``ssm_state``, K =
``ssm_conv``, one group, C = di + 2N conv channels).

FLOPs per token: the input projection, 2 * D * (2 di + 2N + H); the
depthwise convolution, 2 * K * C; the recurrence's own work, about
5 * H * N * P (the decay, the input's outer product with B and its add,
the output contraction with C), not the chunked scan's extra intra-chunk
work; the output projection, 2 * di * D.  Bytes: every weight once (the
two projections, the convolution and its bias, A_log, D, dt_bias, the
gated norm's and the pre-norm's scales); per active row of a decode step
the SSM state read and written in float32, 2 * 4 * H * N * P, and the
conv state read and written in bfloat16, 2 * 2 * (K - 1) * C, whatever
the row's length."""

from __future__ import annotations

ELEM = 2  # bytes of a bfloat16 element
STATE = 4  # bytes of a float32 state element


def _dims(model: dict) -> tuple[int, int, int, int, int, int]:
    D = model["d_model"]
    di = model["ssm_expand"] * D
    P, N = model["ssm_head_dim"], model["ssm_state"]
    return D, di, di // P, P, N, di + 2 * N


def flops(model: dict, tokens: int, pairs: int) -> float:
    D, di, H, P, N, C = _dims(model)
    K = model["ssm_conv"]
    return (2.0 * D * (2 * di + 2 * N + H) + 2.0 * K * C
            + 5.0 * H * N * P + 2.0 * di * D) * tokens


def weight_bytes(model: dict) -> int:
    D, di, H, P, N, C = _dims(model)
    K = model["ssm_conv"]
    return ELEM * (D * (2 * di + 2 * N + H) + K * C + C + 3 * H + di
                   + di * D + D)


def state_bytes(model: dict, cache_lens: list[int]) -> int:
    D, di, H, P, N, C = _dims(model)
    per_row = (2 * STATE * H * N * P
               + 2 * ELEM * (model["ssm_conv"] - 1) * C)
    return per_row * len(cache_lens)
