"""The program's own ``serving.*`` spans in a run's profiler trace.

``ServingEngine`` opens a ``jax.profiler.TraceAnnotation`` for each phase
of a tick (``serving.step`` holding ``serving.admit`` -> ``serving.prefill``,
``serving.scatter``; ``serving.decode``, ``serving.readback``,
``serving.bookkeep``) and passes the layer's counters as its arguments,
which the trace keeps as the event's stats.  ``devtrace.Trace.host`` keeps
names and intervals only; ``load`` reads the stats too.  Both are on the
trace's one clock.  A program without these spans gives no spans, and the
metrics that read them then give nothing."""

from __future__ import annotations

import bisect
import os
from dataclasses import dataclass
from pathlib import Path

import devtrace

PREFIX = "serving."
OUT = Path(__file__).resolve().parent / "out"
# the phases of a decode tick, each a span directly inside ``serving.step``
PHASES = ("serving.admit", "serving.decode", "serving.readback",
          "serving.bookkeep")


@dataclass(frozen=True)
class Span:
    name: str
    start: float                 # seconds, trace clock
    end: float
    args: dict


def load(path: str) -> list[Span]:
    """The ``serving.*`` events of the host plane of a trace file
    (``.xplane.pb``, or gzipped ``.xplane.pb.gz``), in start order."""
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        import gzip
        with gzip.open(path) as f:
            pd = ProfileData.from_serialized_xspace(f.read())
    else:
        pd = ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIX):
                    s = e.start_ns * 1e-9
                    out.append(Span(e.name, s, s + e.duration_ns * 1e-9,
                                    dict(e.stats)))
    out.sort(key=lambda s: s.start)
    return out


def of(ctx) -> list[Span]:
    """The spans of the run's own trace (``bench/out/trace-<pid>``, which
    ``run.py`` removes after the per-layer metrics) inside the traced
    window; read once a run and kept on ``ctx``."""
    got = getattr(ctx, "spans", None)
    if got is None:
        try:
            path = devtrace.find(str(OUT / f"trace-{os.getpid()}"))
        except FileNotFoundError:
            got = []
        else:
            w0, w1 = ctx.trace.window
            got = [s for s in load(path) if w0 <= s.start and s.end <= w1]
        ctx.spans = got
    return got


def _busy_in(iv: list[tuple[float, float]], starts: list[float], lo: float,
             hi: float) -> float:
    """Seconds of [lo, hi] that the sorted, disjoint intervals ``iv``
    (starting at ``starts``) cover."""
    i = max(bisect.bisect_right(starts, lo) - 1, 0)
    tot = 0.0
    while i < len(iv) and iv[i][0] < hi:
        tot += max(0.0, min(iv[i][1], hi) - max(iv[i][0], lo))
        i += 1
    return tot


def decode_steps(tr: devtrace.Trace) -> list[tuple[float, float]]:
    """The ``serving.step`` spans inside the window that hold a
    ``serving.decode`` and no ``serving.prefill``."""
    w0, w1 = tr.window
    host = [h for h in tr.host if h[0].startswith(PREFIX)
            and w0 <= h[1] and h[2] <= w1]

    def starts(name):
        return sorted(h[1] for h in host if h[0] == name)

    dec, pre = starts("serving.decode"), starts("serving.prefill")

    def holds(st, s, e):
        i = bisect.bisect_left(st, s)
        return i < len(st) and st[i] <= e

    return [(s, e) for n, s, e in host if n == "serving.step"
            and holds(dec, s, e) and not holds(pre, s, e)]


def step_idle(tr: devtrace.Trace) -> dict[str, float] | None:
    """Mean seconds per decode tick (``decode_steps``) in which the chips
    ran nothing, split by the phase span that covers them (``PHASES``; the
    rest of the step under ``serving.step``), averaged over the chips.
    None without chips or decode ticks."""
    steps = decode_steps(tr)
    if not tr.ops or not steps:
        return None
    w0, w1 = tr.window
    phases = [h for h in tr.host if h[0] in PHASES
              and w0 <= h[1] and h[2] <= w1]
    phases.sort(key=lambda h: h[1])
    p_starts = [h[1] for h in phases]
    parts = dict.fromkeys(PHASES + ("serving.step",), 0.0)
    for chip in tr.ops.values():
        iv = devtrace.merge([o[1:] for o in chip], w0, w1)
        starts = [s for s, _ in iv]
        for s, e in steps:
            idle = (e - s) - _busy_in(iv, starts, s, e)
            i = bisect.bisect_left(p_starts, s)
            while i < len(phases) and phases[i][1] <= e:
                n, ps, pe = phases[i]
                pe = min(pe, e)
                got = (pe - ps) - _busy_in(iv, starts, ps, pe)
                parts[n] += got
                idle -= got
                i += 1
            parts["serving.step"] += idle
    k = len(steps) * len(tr.ops)
    return {n: v / k for n, v in parts.items()}
