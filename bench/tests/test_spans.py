"""The readers of the engine's ``serving.*`` spans: ``spans.py``,
``metrics/step_idle_ms.py`` and ``metrics/prefill_pad_share.py``."""

import time
from pathlib import Path
from types import SimpleNamespace

import jax
import pytest

import devtrace
import peaks
import run
import spans
import tiny
from metrics import prefill_pad_share, step_idle_ms
from repro.serving.queues import bucket_for, row_bucket

DATA = Path(__file__).parents[1] / "testdata" / "serving-spans.xplane.pb.gz"
MS = 1e-3


def _trace(host, ops):
    """A trace of one chip that ran ``ops``, or of none for None."""
    host = [("bench.traced", 0.0, 1.0)] + host
    chips = {} if ops is None else {"/device:TPU:0": ops}
    return devtrace.Trace((0.0, 1.0), chips, [], host)


def _decode_tick(t0):
    """A 10 ms decode tick from ``t0``: dispatch 1 ms, readback 8 ms,
    bookkeeping 1 ms."""
    return [("serving.step", t0, t0 + 10 * MS),
            ("serving.decode", t0, t0 + 1 * MS),
            ("serving.readback", t0 + 1 * MS, t0 + 9 * MS),
            ("serving.bookkeep", t0 + 9 * MS, t0 + 10 * MS)]


def test_step_idle_is_the_step_less_its_merged_device_ops():
    t0 = 0.1
    # 8 ms of device ops in overlapping pieces, from 0.5 ms into the tick
    ops = [("%a", t0 + 0.5 * MS, t0 + 5 * MS),
           ("%b", t0 + 4 * MS, t0 + 8.5 * MS),
           ("%c", t0 + 6 * MS, t0 + 7 * MS)]
    # a prefill tick with no device op at all, which must not count
    pre = 0.5
    host = _decode_tick(t0) + [
        ("serving.step", pre, pre + 10 * MS),
        ("serving.admit", pre, pre + 9 * MS),
        ("serving.prefill", pre, pre + 2 * MS),
        ("serving.scatter", pre + 2 * MS, pre + 3 * MS),
        ("serving.decode", pre + 9 * MS, pre + 10 * MS)]
    tr = _trace(host, ops)
    assert spans.decode_steps(tr) == [(t0, t0 + 10 * MS)]
    assert step_idle_ms.read(SimpleNamespace(trace=tr)) == \
        pytest.approx(2.0)
    parts = spans.step_idle(tr)
    assert parts["serving.decode"] == pytest.approx(0.5 * MS)
    assert parts["serving.readback"] == pytest.approx(0.5 * MS)
    assert parts["serving.bookkeep"] == pytest.approx(1 * MS)
    assert parts["serving.step"] == pytest.approx(0.0, abs=1e-12)
    assert parts["serving.admit"] == 0.0


def test_step_idle_is_a_mean_over_decode_ticks():
    # two ticks: one with no device op (10 ms idle), one busy throughout
    # (and a device op that runs past its end, clipped to it)
    a, b = 0.1, 0.2
    ops = [("%x", b - 1 * MS, b + 12 * MS)]
    tr = _trace(_decode_tick(a) + _decode_tick(b), ops)
    assert step_idle_ms.read(SimpleNamespace(trace=tr)) == \
        pytest.approx(5.0)
    # the step's own code, outside every phase span, counts too
    host = [("serving.step", a, a + 10 * MS),
            ("serving.decode", a + 2 * MS, a + 3 * MS)]
    tr = _trace(host, [("%y", a + 2.5 * MS, a + 4 * MS)])
    parts = spans.step_idle(tr)
    assert parts["serving.step"] == pytest.approx(8 * MS)
    assert parts["serving.decode"] == pytest.approx(0.5 * MS)
    assert step_idle_ms.read(SimpleNamespace(trace=tr)) == \
        pytest.approx(8.5)


def test_step_idle_reads_nothing_without_chips_or_decode_ticks():
    tr = _trace(_decode_tick(0.1), None)
    assert step_idle_ms.read(SimpleNamespace(trace=tr)) is None
    # a program without the engine's spans: only the driver's own
    tr = _trace([("bench.tick", 0.1, 0.2)], [("%a", 0.1, 0.15)])
    assert step_idle_ms.read(SimpleNamespace(trace=tr)) is None
    # a tick outside the window
    tr = _trace(_decode_tick(0.995), [("%a", 0.1, 0.15)])
    assert step_idle_ms.read(SimpleNamespace(trace=tr)) is None


def test_pad_share_reads_nothing_without_a_trace(tmp_path):
    ctx = SimpleNamespace(trace=_trace([], None))
    assert spans.of(ctx) == [] and ctx.spans == []
    assert prefill_pad_share.read(ctx) is None


def test_pad_share_of_known_prefills():
    ctx = SimpleNamespace(spans=[
        spans.Span("serving.prefill", 0.1, 0.2,
                   {"rows": 2, "width": 4, "bucket": 32,
                    "real_tokens": 40}),
        spans.Span("serving.prefill", 0.3, 0.4,
                   {"rows": 1, "width": 4, "bucket": 16,
                    "real_tokens": 8}),
        spans.Span("serving.decode", 0.4, 0.5, {"rows": 3})])
    assert prefill_pad_share.read(ctx) == pytest.approx(
        100 * (1 - 48 / (128 + 64)))


@pytest.fixture
def v5e_peaks(monkeypatch):
    monkeypatch.setattr(peaks, "lookup",
                        lambda kind: peaks.TABLE["devices"]["TPU v5 lite"])


def test_pad_share_through_run_on_the_cpu(v5e_peaks, monkeypatch):
    seen = {}
    read = run.per_layer

    def per_layer(spec, cell, ctx):
        seen["ctx"] = ctx
        return read(spec, cell, ctx)

    monkeypatch.setattr(run, "per_layer", per_layer)
    spec = dict(tiny.SPEC, per_layer=[
        {"name": "step_idle_ms", "unit": "ms"},
        {"name": "prefill_pad_share", "unit": "%"}])
    res = run.execute(spec, "tiny", tiny.CONF, tiny.MIX, 2**31 + 11, 2.0,
                      True, jax.devices()[:1], time.perf_counter())
    # no TPU planes on the CPU: the host loop's metric reads nothing
    assert set(res["metrics"]) == {"prefill_pad_share"}
    got = res["metrics"]["prefill_pad_share"]
    assert got["unit"] == "%" and 0 < got["value"] < 100
    # the spans' counters agree with what the driver sees from outside
    ctx = seen["ctx"]
    pre = [s.args for s in ctx.spans if s.name == "serving.prefill"]
    ticks = [t["prefill"] for t in ctx.ticks if t["prefill"]]
    assert pre and len(pre) == len(ticks)
    assert [a["real_tokens"] for a in pre] == [sum(t) for t in ticks]
    buckets = (16, 32, 64, 128)
    assert [a["bucket"] for a in pre] == \
        [bucket_for(max(t), buckets) for t in ticks]
    # the prefill computes the row bucket of the tick's admissions
    rows = [row_bucket(len(t), tiny.CONF["width"]) for t in ticks]
    assert [a["width"] for a in pre] == rows
    assert got["value"] == pytest.approx(100 * (1 - sum(map(sum, ticks)) / (
        sum(r * bucket_for(max(t), buckets) for r, t in zip(rows, ticks)))))
    dec = [s.args["rows"] for s in ctx.spans if s.name == "serving.decode"]
    assert dec == [len(t["decode"]) for t in ctx.ticks if t["decode"]]


def test_recorded_chip_trace():
    got = spans.load(str(DATA))
    names = {s.name for s in got}
    assert {"serving.step", "serving.admit", "serving.prefill",
            "serving.scatter", "serving.decode", "serving.readback",
            "serving.bookkeep"} <= names
    for s in got:
        if s.name == "serving.prefill":
            assert {"rows", "width", "bucket", "real_tokens"} <= set(s.args)
            assert s.args["real_tokens"] <= s.args["width"] * s.args["bucket"]
        if s.name in ("serving.decode", "serving.scatter"):
            assert s.args["rows"] >= 1
    tr = devtrace.load(str(DATA))
    steps = [e - s for n, s, e in tr.host if n == "serving.step"
             and tr.window[0] <= s and e <= tr.window[1]]
    idle = step_idle_ms.read(SimpleNamespace(trace=tr))
    assert 0 < idle < sum(steps) / len(steps) * 1e3
