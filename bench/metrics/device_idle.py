"""Share of the traced window in which no operation ran on the chip:
1 - (union of the device-op intervals) / (window)."""

import devtrace as trace


def read(ctx):
    w = ctx.trace.window_s
    return 100.0 * (1.0 - trace.busy_s(ctx.trace) / w) if w > 0 else None
