"""Record a small profiler trace of the tiny test configuration served on
the chip, print the planes and lines it holds, and copy it to OUT (the
test data of the trace reduction).

    python3 bench/tools/record_trace.py OUT.xplane.pb
"""

from __future__ import annotations

import glob
import shutil
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH / "tests"), str(BENCH.parent / "src")]


def main() -> None:
    import jax
    from jax.profiler import ProfileData
    import tiny
    from drivers import lm_serving
    t0 = time.perf_counter()
    mix = dict(tiny.MIX, lead_in_s=0.5)
    res = lm_serving.run(tiny.CONF, mix, 5, 4.0, True, t0,
                         str(BENCH / "out"), jax.devices()[:1])
    path = sorted(glob.glob(f"{res.trace_dir}/**/*.xplane.pb",
                            recursive=True))[-1]
    shutil.copy(path, sys.argv[1])
    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        lines = [(ln.name, sum(1 for _ in ln.events)) for ln in plane.lines]
        print("plane", plane.name, lines[:12])
        for ln in plane.lines:
            evs = list(ln.events)
            for e in evs[:3]:
                print("   ", ln.name, "|", e.name[:80], e.start_ns,
                      e.duration_ns)
    print("ticks", len(res.ctx.ticks), res.ctx.ticks[:3])


if __name__ == "__main__":
    main()
