"""Production serving plane: trace-driven, frontier-placed request plane.

Layered package (split out of the old single-file engine):

* :mod:`repro.serving.requests` — request lifecycle + open-loop arrival
  traces (seeded Poisson / bursty-diurnal generators)
* :mod:`repro.serving.queues` — bounded stage queues, prompt buckets, and
  the KV cache slot pool
* :mod:`repro.serving.router` — trace-driven request router over a
  frontier operating point (admission control, SLO shedding, replica
  load balancing, live operating-point swaps)
* :mod:`repro.serving.metrics` — p50/p99 latency, TTFT, goodput vs SLO,
  queue-depth histograms
* :mod:`repro.serving.sim` — closed-form pipeline throughput simulation
* :mod:`repro.serving.engine` — the continuous-batching model-serving
  engine, rebuilt on the layers above (also the compatibility surface:
  every old ``repro.serving.engine`` import keeps working)
"""

from .engine import (KVCachePool, Request, ServingEngine, ServingStats,
                     simulate_pipeline_throughput)
from .metrics import PlaneReport, mean, percentile
from .queues import PROMPT_BUCKETS, StageQueue, bucket_for, row_bucket
from .requests import (Arrival, arrivals_to_requests, bursty_diurnal_trace,
                       empirical_rate, poisson_trace)
from .router import ExecutorBackend, RoutedRequest, Router, VirtualBackend

__all__ = [
    "Arrival", "ExecutorBackend", "KVCachePool", "PROMPT_BUCKETS",
    "PlaneReport", "Request", "RoutedRequest", "Router", "ServingEngine",
    "ServingStats", "StageQueue", "VirtualBackend", "arrivals_to_requests",
    "bucket_for", "bursty_diurnal_trace", "empirical_rate", "mean",
    "percentile", "poisson_trace", "row_bucket",
    "simulate_pipeline_throughput",
]
