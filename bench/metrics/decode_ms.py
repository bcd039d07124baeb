"""Mean device time of one run of the compiled decode step
(``jit_decode_step``) in the traced window."""

import devtrace as trace


def read(ctx):
    ts = trace.module_times(ctx.trace, "jit_decode_step")
    return sum(ts) / len(ts) * 1e3 if ts else None
