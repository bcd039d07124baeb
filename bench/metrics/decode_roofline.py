"""The decode step's share of its HBM roofline: the bytes that the traced
decode steps need (``costs.decode_bytes``: every weight with the published
expert count, the K/V of each active row's filled positions, one position
written per row) at the peak HBM bandwidth, over their device time."""

import costs
import devtrace as trace


def read(ctx):
    ts = trace.module_times(ctx.trace, "jit_decode_step")
    need = sum(costs.decode_bytes(ctx.model, t["decode"])
               for t in ctx.ticks if t["decode"])
    if not ts or not need:
        return None
    return 100.0 * need / ctx.peaks["hbm_bytes_per_s"] / sum(ts)
