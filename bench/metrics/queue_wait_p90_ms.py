"""90th percentile of the admission queue's wait (the engine's
``admitted_at`` less the due time) over the requests due between the
window's start and the end of the profiled span; the profiler's stop
stalls the loop after it."""

from stats import percentile


def read(ctx):
    ws, (_, end) = ctx.window[0], ctx.traced
    waits = [r.admitted_at - r.submitted_at for r in ctx.reqs.values()
             if ws <= r.submitted_at < end and r.admitted_at is not None]
    return percentile(waits, 90) * 1e3 if waits else None
