"""Share of the slot pool that holds live state while the engine decodes:
100 x Σ (``kv_used`` + ``state_used``) / Σ ``reserved`` over the
``serving.step`` spans in the traced window that hold a
``serving.decode`` (the span's own counters: K/V bytes at the active
slots' filled positions, recurrent-state bytes of the active slots, and
the pool's bytes).  A program whose steps carry no such counters gives
nothing."""

import bisect

import spans


def read(ctx):
    got = spans.of(ctx)
    dec = sorted(s.start for s in got if s.name == "serving.decode")
    used = room = 0
    for s in got:
        if s.name != "serving.step" or "reserved" not in s.args:
            continue
        i = bisect.bisect_left(dec, s.start)
        if i < len(dec) and dec[i] <= s.end:
            used += s.args["kv_used"] + s.args["state_used"]
            room += s.args["reserved"]
    if not room:
        return None
    return 100.0 * used / room
