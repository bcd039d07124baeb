"""Reduction of a JAX profiler trace (``*.xplane.pb``) to what the per-layer
metrics read: device-op intervals, compiled-program (module) executions,
and host spans, on the trace's one clock.

Planes whose name starts with ``/device:TPU:`` are chips: their ``XLA
Ops`` line holds every operation that ran, their ``XLA Modules`` line
every run of a compiled program (``jit_decode_step``, ...).  The host
plane ``/host:CPU`` holds the host threads' spans, among them the
benchmark's own ``bench.*`` annotations.  The measured window is the span
``bench.traced`` that the driver opens after the profiler has started and
closes before it stops."""

from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field

WINDOW_SPAN = "bench.traced"


@dataclass
class Trace:
    window: tuple[float, float]                 # seconds, trace clock
    ops: dict[str, list[tuple[str, float, float]]]  # chip -> op runs
    modules: list[tuple[str, float, float]]     # (name, start, end)
    host: list[tuple[str, float, float]] = field(default_factory=list)

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]


def find(dirname: str) -> str:
    paths = sorted(glob.glob(os.path.join(dirname, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {dirname}")
    return paths[-1]


def load(path: str) -> Trace:
    """Read a trace file (``.xplane.pb``, or gzipped ``.xplane.pb.gz``)."""
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        import gzip
        with gzip.open(path) as f:
            pd = ProfileData.from_serialized_xspace(f.read())
    else:
        pd = ProfileData.from_file(path)
    ops: dict[str, list[tuple[str, float, float]]] = {}
    modules: list[tuple[str, float, float]] = []
    host: list[tuple[str, float, float]] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and "SparseCore" not in \
                plane.name:
            iv = ops.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name == "XLA Ops":
                    for e in line.events:
                        s, d = e.start_ns * 1e-9, e.duration_ns * 1e-9
                        # "%fusion.12 = bf16[...] fusion(...)" -> "%fusion.12"
                        iv.append((e.name.split(" = ", 1)[0], s, s + d))
                elif line.name == "XLA Modules":
                    for e in line.events:
                        s = e.start_ns * 1e-9
                        modules.append((e.name, s, s + e.duration_ns * 1e-9))
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    s = e.start_ns * 1e-9
                    host.append((e.name, s, s + e.duration_ns * 1e-9))
    spans = [h for h in host if h[0] == WINDOW_SPAN]
    if not spans:
        raise ValueError(f"trace has no {WINDOW_SPAN} span")
    _, w0, w1 = spans[0]
    return Trace((w0, w1), ops, modules, host)


def merge(iv: list[tuple[float, float]], lo: float, hi: float
          ) -> list[tuple[float, float]]:
    """Union of intervals, clipped to [lo, hi], sorted."""
    out: list[list[float]] = []
    for s, e in sorted(iv):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_s(tr: Trace) -> float:
    """Seconds of the window in which some operation ran on a chip,
    averaged over the chips."""
    if not tr.ops:
        return 0.0
    tot = sum(sum(e - s for s, e in merge([o[1:] for o in iv], *tr.window))
              for iv in tr.ops.values())
    return tot / len(tr.ops)


def module_times(tr: Trace, prefix: str) -> list[float]:
    """Device seconds of each run, inside the window, of the compiled
    programs whose name starts with ``prefix``."""
    w0, w1 = tr.window
    return [e - s for n, s, e in tr.modules
            if n.startswith(prefix) and s >= w0 and e <= w1]


def idle_gaps(tr: Trace, top: int = 10) -> list[list]:
    """The longest gaps in which the first chip ran nothing, each named by
    the innermost host span that covers its middle."""
    if not tr.ops:
        return []
    iv = merge([o[1:] for o in next(iter(tr.ops.values()))], *tr.window)
    edges = [tr.window[0]] + [x for s, e in iv for x in (s, e)] + \
        [tr.window[1]]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[1] - g[0], reverse=True)
    out = []
    for s, e in gaps[:top]:
        mid = 0.5 * (s + e)
        cover = [h for h in tr.host if h[1] <= mid <= h[2]
                 and h[0] != WINDOW_SPAN]
        name = min(cover, key=lambda h: h[2] - h[1])[0] if cover else "none"
        out.append([name, e - s])
    return out


def top_ops(tr: Trace, top: int = 10) -> list[list]:
    """Device seconds by operation name inside the window, summed over the
    chips, the largest first."""
    w0, w1 = tr.window
    tot: dict[str, float] = {}
    for iv in tr.ops.values():
        for n, s, e in iv:
            d = min(e, w1) - max(s, w0)
            if d > 0:
                tot[n] = tot.get(n, 0.0) + d
    return [[n, s] for n, s in sorted(tot.items(), key=lambda x: -x[1])[:top]]
