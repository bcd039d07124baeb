"""Share of the prefilled positions that are padding: 100 x (1 - the
prompt positions the rows hold / width x bucket), summed over the
``serving.prefill`` spans in the traced window (``real_tokens``,
``width``, ``bucket``: the span's own counters)."""

import spans


def read(ctx):
    pre = [s.args for s in spans.of(ctx) if s.name == "serving.prefill"]
    room = sum(a["width"] * a["bucket"] for a in pre)
    if not room:
        return None
    return 100.0 * (1.0 - sum(a["real_tokens"] for a in pre) / room)
