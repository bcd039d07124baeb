"""Readings that a cell's limits are set from: the program's gap numbers
(``drivers/lm_serving.py:gap_stats``) and the float8 control's on the same
requests, on many seeds, each seed a full run of the cell (set-up, lead-in,
a short window, the check) in one process.  The control is also judged by
the cell's own limits (``control_correct``); the tool exits 1 if it ever
comes out correct.

    python3 bench/tools/readings.py --workload moe-chat --seconds 15 \\
        --seeds 1,2,3 --out chiprun_out/readings.jsonl
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    import jax
    import run
    from drivers import lm_serving
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("readings are taken on the chip")
    spec = json.loads((run.ROOT / "BENCHMARK.json").read_text())
    _, conf, mix = run.load_cell(spec, args.workload)
    run.OUT.mkdir(exist_ok=True)
    sound = True
    with open(args.out, "a") as f:
        for seed in [int(s) for s in args.seeds.split(",")]:
            t0 = time.perf_counter()
            res = lm_serving.run(conf, mix, seed, args.seconds, False, t0,
                                 str(run.OUT), jax.devices()[:1],
                                 control=True)
            ctl = lm_serving.passed(res.gaps["control_checks"])
            sound &= res.correct and not ctl
            line = {"workload": args.workload, "seed": seed,
                    "correct": res.correct, "control_correct": ctl,
                    **{k: v["value"] for k, v in res.checks.items()},
                    **{f"program_{k}": v
                       for k, v in res.gaps["program"].items()},
                    **{f"control_{k}": v
                       for k, v in res.gaps["control"].items()},
                    **res.metrics, **res.info}
            print(json.dumps(line), flush=True)
            f.write(json.dumps(line) + "\n")
    if not sound:
        raise SystemExit("a program run was not correct, or the control "
                         "was correct under the cell's limits")


if __name__ == "__main__":
    main()
