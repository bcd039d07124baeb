"""Find a cell's knee: serve its mix at each of several fixed rates, one
window each, on one engine, and print for each rate the requests due and
admitted in the window and the queue at its start and end.  The knee is
the highest rate whose backlog does not grow across the window.

    python3 bench/tools/sweep.py --workload moe-chat --seconds 40 \\
        --rates 0.8,1.2,1.6 --seed 7 --out chiprun_out/sweep.jsonl
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
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    import jax
    import run
    from drivers import lm_serving as d
    from repro.launch.cache import enable_compile_cache
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("the sweep runs on the chip")
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    spec = json.loads((run.ROOT / "BENCHMARK.json").read_text())
    _, conf, mix = run.load_cell(spec, args.workload)
    engine, _ = d.build(conf, args.seed)
    d.warm(engine, mix, conf["model"]["vocab"])
    with open(args.out, "a") as f:
        for rate in [float(r) for r in args.rates.split(",")]:
            m = dict(mix, rate_rps=rate)
            w, ws, we = d.serve_window(engine, m, args.seed, args.seconds,
                                       conf["model"]["vocab"])
            e2e = d.end_to_end(w, ws, we)
            reqs = list(w.reqs.values())

            def queued(t):
                return sum(1 for r in reqs if r.submitted_at <= t and
                           (r.admitted_at is None or r.admitted_at > t))

            line = {"workload": args.workload, "rate_rps": rate,
                    "due": e2e["_due"],
                    "admitted": sum(1 for r in reqs if r.admitted_at
                                    and ws <= r.admitted_at <= we),
                    "queued_start": queued(ws), "queued_end": queued(we),
                    "ttft_p90_ms": e2e["ttft_p90_ms"],
                    "itl_p95_ms": e2e["itl_p95_ms"],
                    "out_tok_s": e2e["out_tok_s"]}
            print(json.dumps(line), flush=True)
            f.write(json.dumps(line) + "\n")
            t0 = time.perf_counter()
            while engine.queue or engine.active:     # drain
                engine.run(max_steps=64)
            print(f"drained in {time.perf_counter() - t0:.1f}s", flush=True)


if __name__ == "__main__":
    main()
