"""The decode step's share of the chip's peak FLOP/s: the FLOPs that the
active rows of the traced decode steps need (``costs.decode_flops``), over
their device time times the peak."""

import costs
import devtrace as trace


def read(ctx):
    ts = trace.module_times(ctx.trace, "jit_decode_step")
    flops = sum(costs.decode_flops(ctx.model, t["decode"])
                for t in ctx.ticks if t["decode"])
    if not ts or not flops:
        return None
    return 100.0 * flops / (sum(ts) * ctx.peaks["bf16_flops"])
