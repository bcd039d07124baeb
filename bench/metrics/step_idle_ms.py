"""Mean time per decode tick in which the chip waits on the engine's own
host code: over the ``serving.step`` spans in the traced window that hold
a ``serving.decode`` and no ``serving.prefill``, the span's length less
the union of the chip's device-op intervals inside it (``spans.
step_idle``)."""

import spans


def read(ctx):
    parts = spans.step_idle(ctx.trace)
    return sum(parts.values()) * 1e3 if parts else None
