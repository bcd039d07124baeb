"""Benchmark harness — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--full]

Prints a human-readable report per table plus a machine-readable
``name,us_per_call,derived`` CSV at the end.
"""

from __future__ import annotations

import argparse
import sys


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="all 18 CNNs / all scenarios (slower)")
    args = ap.parse_args()
    quick = not args.full

    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    from . import (bench_autotune, bench_elastic, bench_overhead,
                   bench_partitions, bench_query, bench_roofline, bench_zoo)

    rows = []
    rows += bench_zoo.run(quick)            # Table I
    rows += bench_overhead.run(quick)       # Table III
    rows += bench_partitions.run(quick)     # Figs 6-15 + Table IV
    rows += bench_query.run(quick)          # <50ms query claim
    rows += bench_elastic.run(quick)        # motivation (vi): re-planning
    rows += bench_roofline.run(quick)       # §Roofline (from dry-run)
    rows += bench_autotune.run(quick)       # kernel block-size autotuning

    print("\nname,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")

    if bench_partitions.scenario_throughput.failures:
        print("FAILED predicted-vs-simulated throughput validation: "
              + ", ".join(bench_partitions.scenario_throughput.failures))
        sys.exit(1)


if __name__ == "__main__":
    main()
