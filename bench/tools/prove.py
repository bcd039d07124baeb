"""Prove one cell on the chip, in one call: find its knee, set its rate,
take the readings its limits are set from, set the limits, then run the
two full sets and the traced runs.  Each step is a fresh process (this one
never touches JAX, so each child gets the chip); every output lands in
OUT.

    python3 bench/tools/prove.py --workload moe-chat --rates 0.6,0.9,1.2 \\
        --out chiprun_out/prove-moe-chat

Knee: the highest swept rate, below the first that fails, whose window
admitted all but two of the requests due in it and whose admission queue
grew by at most one across it.  Rate: 0.8 x knee, written into the cell's
traffic file.  Limits, for the first of ``token_gap``, ``gap_mean``,
``flip_share`` whose smallest control reading is at least three times
its largest program reading: lower + 0.6 x (upper - lower), written into
``bench/limits/<workload>.json``."""

from __future__ import annotations

import argparse
import json
import random
import subprocess
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
STATS = ("token_gap", "gap_mean", "flip_share")


def sh(cmd: list[str], log: Path, timeout: float) -> int:
    t0 = time.perf_counter()
    with open(log, "a") as f:
        f.write(f"$ {' '.join(cmd)}\n")
        f.flush()
        try:
            rc = subprocess.run(cmd, cwd=ROOT, stdout=f, stderr=f,
                                timeout=timeout).returncode
        except subprocess.TimeoutExpired:
            rc = 124
    print(f"{' '.join(cmd[1:4])} ... rc={rc} "
          f"{time.perf_counter() - t0:.0f}s", flush=True)
    return rc


def jsonl(path: Path) -> list[dict]:
    if not path.exists():
        return []
    return [json.loads(x) for x in path.read_text().splitlines()
            if x.startswith("{")]


def knee(lines: list[dict]) -> tuple[float, bool]:
    best, clean = None, True
    for ln in sorted(lines, key=lambda x: x["rate_rps"]):
        ok = (ln["admitted"] >= ln["due"] - 2 and
              ln["queued_end"] - ln["queued_start"] <= 1)
        if not ok:
            clean = False
            break
        best = ln["rate_rps"]
    return best, clean


def limits(lines: list[dict]) -> tuple[dict, dict]:
    seen = {}
    for s in STATS:
        lower = max(x[f"program_{s}"] for x in lines)
        upper = min(x[f"control_{s}"] for x in lines)
        seen[s] = {"lower": lower, "upper": upper}
        if upper > 0 and upper >= 3 * lower:
            return {s: lower + 0.6 * (upper - lower)}, seen
    return {}, seen


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--rate", type=float, help="skip the sweep")
    ap.add_argument("--sweep-seconds", type=float, default=30)
    ap.add_argument("--readings", type=int, default=12)
    ap.add_argument("--readings-seconds", type=float, default=15)
    ap.add_argument("--skip-readings", action="store_true")
    ap.add_argument("--set-runs", type=int, default=6)
    ap.add_argument("--traced", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=51)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", required=True)
    a = ap.parse_args()
    out = Path(a.out)
    out.mkdir(parents=True, exist_ok=True)
    log = out / "log.txt"
    rnd = random.Random(a.seed or time.time_ns())
    seeds = lambda n: [rnd.randrange(2**30, 2**32 + 2**24) for _ in range(n)]
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = next(c for c in spec["workloads"] if c["name"] == a.workload)
    tfile = BENCH / "traffic" / f"{cell['traffic']}.json"
    lfile = BENCH / "limits" / f"{a.workload}.json"
    py = sys.executable
    decide = {"workload": a.workload}

    if a.rate is None:
        sw = out / "sweep.jsonl"
        sh([py, "bench/tools/sweep.py", "--workload", a.workload,
            "--seconds", str(a.sweep_seconds), "--rates", a.rates,
            "--seed", str(seeds(1)[0]), "--out", str(sw)], log, 1500)
        k, clean = knee(jsonl(sw))
        if k is None:
            print("no swept rate was below the knee", flush=True)
            return
        decide.update(knee=k, knee_below_first_failure=not clean)
        a.rate = round(0.8 * k, 3)
    mix = json.loads(tfile.read_text())
    mix["rate_rps"] = a.rate
    tfile.write_text(json.dumps(mix, indent=2) + "\n")
    decide["rate_rps"] = a.rate
    print(json.dumps(decide), flush=True)

    if not a.skip_readings:
        rd = out / "readings.jsonl"
        sh([py, "bench/tools/readings.py", "--workload", a.workload,
            "--seconds", str(a.readings_seconds), "--seeds",
            ",".join(map(str, seeds(a.readings))), "--out", str(rd)],
           log, 2400)
        lim, seen = limits(jsonl(rd))
        decide.update(readings=seen, limits=lim)
        print(json.dumps(decide), flush=True)
        if not lim:
            (out / "decisions.json").write_text(json.dumps(decide, indent=1))
            raise SystemExit("no number separates the program from its "
                             "control: no limit written")
        lfile.write_text(json.dumps(lim) + "\n")
    (out / "decisions.json").write_text(json.dumps(decide, indent=1))

    runs = out / "runs.jsonl"
    set_seeds = seeds(a.set_runs)
    plan = [(s, 0, f"set{i}") for i in (1, 2) for s in set_seeds] + \
        [(s, 1, "traced") for s in seeds(a.traced)]
    for s, tr, tag in plan:
        res = out / f"run-{tag}-{s}.out"
        rc = sh([py, "bench/run.py", "--workload", a.workload, "--seed",
                 str(s), "--seconds", str(a.seconds), "--trace", str(tr)],
                res, 400)
        last = [x for x in res.read_text().splitlines()
                if x.startswith("{")]
        line = {"tag": tag, "seed": s, "rc": rc,
                "result": json.loads(last[-1]) if last else None}
        with open(runs, "a") as f:
            f.write(json.dumps(line) + "\n")
        r = line["result"] or {}
        print(tag, s, rc, r.get("correct"),
              {k: round(v["value"], 3)
               for k, v in r.get("metrics", {}).items()}, flush=True)


if __name__ == "__main__":
    main()
