"""The prefill step's share of the chip's peak FLOP/s: the FLOPs that the
real prompt tokens prefilled in the traced window need (``costs.
prefill_flops``; no padding rows or positions), over the device time of
the traced prefill runs times the peak."""

import costs
import devtrace as trace


def read(ctx):
    ts = trace.module_times(ctx.trace, "jit_prefill_step")
    flops = sum(costs.prefill_flops(ctx.model, n)
                for t in ctx.ticks for n in t["prefill"])
    if not ts or not flops:
        return None
    return 100.0 * flops / (sum(ts) * ctx.peaks["bf16_flops"])
