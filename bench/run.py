"""Run one cell of the benchmark once and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

From the root of a checkout.  The cell (``BENCHMARK.json`` ``workloads``)
names a configuration (``bench/configs/<config>.json``, whose ``driver``
names ``bench/drivers/<driver>.py``) and a traffic mix
(``bench/traffic/<mix>.json``), and the cell's limits on the numbers
that decide ``correct`` are in ``bench/limits/<workload>.json``.  With
``--trace 0`` the result carries the cell's end-to-end metrics; with
``--trace 1`` the per-layer metrics, each read by
``bench/metrics/<metric>.py`` from the run's records and its profiler
trace.  The last lines on standard error, and the result's last
key, give each number compared for ``correct`` beside its limit.  Refuses
to run (exit 2, no result) without a TPU or with fewer chips than the cell
asks for."""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
OUT = BENCH / "out"


def fail(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)
    sys.exit(2)


def load_cell(spec: dict, name: str):
    cells = {c["name"]: c for c in spec["workloads"]}
    if name not in cells:
        fail(f"no workload {name!r}; have {sorted(cells)}")
    cell = cells[name]
    confs = {c["name"]: c for c in spec["configs"]}
    conf = json.loads((ROOT / confs[cell["config"]]["file"]).read_text())
    mix = json.loads((BENCH / "traffic" / f"{cell['traffic']}.json")
                     .read_text())
    conf["limits"] = json.loads((BENCH / "limits" / f"{name}.json")
                                .read_text())
    return cell, conf, mix


def metric_names(spec: dict, cell: str, trace: bool) -> list[dict]:
    """The cell's metrics: those whose ``workloads`` name it, and those
    with no ``workloads``."""
    group = spec["per_layer"] if trace else spec["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def per_layer(spec: dict, cell: str, ctx) -> dict:
    out = {}
    for m in metric_names(spec, cell, True):
        mod = importlib.import_module(f"metrics.{m['name']}")
        v = mod.read(ctx)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def execute(spec: dict, name: str, conf: dict, mix: dict, seed: int,
            seconds: float, trace: bool, devices, t_start: float) -> dict:
    """Run cell ``name`` of configuration ``conf`` under ``mix`` on
    ``devices`` and return its result object (everything after the check
    for a chip)."""
    import peaks
    import devtrace as tr
    driver = importlib.import_module(f"drivers.{conf['driver']}")
    OUT.mkdir(exist_ok=True)
    res = driver.run(conf, mix, seed, seconds, trace, t_start, str(OUT),
                     devices)
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": int(res.memory_peak_bytes)}
    out = {"correct": bool(res.correct), "attempted": int(res.attempted),
           "failed": int(res.failed)}
    if trace:
        ctx = res.ctx
        ctx.peaks = peaks.lookup(dev.device_kind)
        ctx.trace = tr.load(tr.find(res.trace_dir))
        metrics = per_layer(spec, name, ctx)
        device["busy_s"] = tr.busy_s(ctx.trace)
        device["window_s"] = ctx.trace.window_s
        out["breakdown"] = {"device_ops": tr.top_ops(ctx.trace),
                            "idle_gaps": tr.idle_gaps(ctx.trace)}
        shutil.rmtree(res.trace_dir, ignore_errors=True)
    else:
        metrics = {m["name"]: {"value": res.metrics[m["name"]],
                               "unit": m["unit"]}
                   for m in metric_names(spec, name, False)}
    out["metrics"] = metrics
    out["device"] = device
    out["info"] = res.info
    out["checks"] = res.checks
    return out


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    spec_path = ROOT / "BENCHMARK.json"
    if not spec_path.is_file():
        fail("no BENCHMARK.json at the root of this checkout")
    if not (ROOT / "src" / "repro").is_dir():
        fail("the program (src/repro) is not in this checkout")
    spec = json.loads(spec_path.read_text())
    cell, conf, mix = load_cell(spec, args.workload)
    sys.path[:0] = [str(BENCH), str(ROOT / "src")]
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        fail(f"no TPU: JAX runs on {devs[0].platform}")
    if len(devs) < cell["chips"]:
        fail(f"cell {args.workload} needs {cell['chips']} chips, JAX "
             f"finds {len(devs)}")
    res = execute(spec, args.workload, conf, mix, args.seed, args.seconds,
                  bool(args.trace), devs[:cell["chips"]], T_START)
    for k, v in res["checks"].items():
        print(f"check {k} {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(res), flush=True)


if __name__ == "__main__":
    main()
