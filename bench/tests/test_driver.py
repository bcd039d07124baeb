"""CPU rehearsal of the lm_serving driver at toy sizes, and the chip check
of ``run.py`` itself."""

import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import jax
import pytest

import peaks
import run
import tiny

ROOT = Path(__file__).resolve().parents[2]


@pytest.fixture
def v5e_peaks(monkeypatch):
    # the CPU has no entry in the table of peaks, as it must not
    monkeypatch.setattr(peaks, "lookup",
                        lambda kind: peaks.TABLE["devices"]["TPU v5 lite"])


@pytest.mark.parametrize("trace", [False, True], ids=["trace0", "trace1"])
def test_last_line_keys(trace, v5e_peaks):
    res = run.execute(tiny.SPEC, "tiny", tiny.CONF, tiny.MIX, 2**31 + 7,
                      2.0, trace, jax.devices()[:1], time.perf_counter())
    json.dumps(res)
    keys = list(res)
    assert keys[-1] == "checks"
    for k in ("correct", "attempted", "failed", "metrics", "device"):
        assert k in res
    assert res["attempted"] > 0 and res["failed"] == 0
    dev = res["device"]
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(dev)
    if trace:
        assert {"busy_s", "window_s"} <= set(dev)
        assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
        assert {"device_idle", "queue_wait_p90_ms"} <= set(res["metrics"])
        assert "itl_p95_ms" not in res["metrics"]
    else:
        assert set(res["metrics"]) == {"ttft_p50_ms", "itl_p95_ms",
                                       "setup_s"}
        assert all(m["value"] > 0 for m in res["metrics"].values())
    assert res["info"]["compiles_in_window"] == 0
    assert res["checks"]["token_gap"]["limit"] == 0.05


def test_a_metric_is_reported_in_the_cells_it_names():
    spec = {"end_to_end": [{"name": "a", "workloads": ["x"]}, {"name": "b"}],
            "per_layer": [{"name": "c", "workloads": ["y"]}]}
    assert [m["name"] for m in run.metric_names(spec, "x", False)] == \
        ["a", "b"]
    assert [m["name"] for m in run.metric_names(spec, "y", False)] == ["b"]
    assert [m["name"] for m in run.metric_names(spec, "x", True)] == []
    assert [m["name"] for m in run.metric_names(spec, "y", True)] == ["c"]


def _run(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "moe-chat", "--seed",
         "1", "--seconds", "1", "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_refuses_the_cpu():
    p = _run(ROOT)
    assert p.returncode == 2
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_run_refuses_a_checkout_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_benchmark_json_names_every_file():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for c in spec["configs"]:
        conf = json.loads((ROOT / c["file"]).read_text())
        assert conf["name"] == c["name"]
        assert (ROOT / "bench" / "drivers" / f"{conf['driver']}.py").is_file()
    for w in spec["workloads"]:
        assert (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").is_file()
        assert (ROOT / "bench" / "limits" / f"{w['name']}.json").is_file()
    for m in spec["per_layer"]:
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file()
