"""Mean device time of one run of the compiled prefill step
(``jit_prefill_step``) in the traced window."""

import devtrace as trace


def read(ctx):
    ts = trace.module_times(ctx.trace, "jit_prefill_step")
    return sum(ts) / len(ts) * 1e3 if ts else None
