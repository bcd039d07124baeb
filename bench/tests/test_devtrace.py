"""The trace reduction on a trace recorded on a TPU v5e: the tiny test
configuration served for 4 s (``bench/tools/record_trace.py``)."""

from pathlib import Path

import pytest

import devtrace

DATA = str(Path(__file__).parents[1] / "testdata" / "tiny.xplane.pb.gz")


@pytest.fixture(scope="module")
def tr():
    return devtrace.load(DATA)


def test_planes_and_window(tr):
    assert list(tr.ops) == ["/device:TPU:0"]
    assert 1.5 < tr.window_s < 2.5          # the 2 s traced span
    assert tr.ops["/device:TPU:0"]
    assert all(not n.count(" = ") for n, _, _ in tr.ops["/device:TPU:0"])


def test_modules_inside_the_window(tr):
    dec = devtrace.module_times(tr, "jit_decode_step")
    pre = devtrace.module_times(tr, "jit_prefill_step")
    assert len(dec) > 10 and pre
    assert all(0 < t < tr.window_s for t in dec + pre)
    w0, w1 = tr.window
    inside = [m for m in tr.modules if m[0].startswith("jit_decode_step")
              and m[1] >= w0 and m[2] <= w1]
    assert len(inside) == len(dec)


def test_busy_is_the_union_of_op_intervals(tr):
    busy = devtrace.busy_s(tr)
    assert 0 < busy < tr.window_s
    iv = devtrace.merge([o[1:] for o in tr.ops["/device:TPU:0"]],
                        *tr.window)
    assert all(a[1] <= b[0] for a, b in zip(iv, iv[1:]))
    assert busy == pytest.approx(sum(e - s for s, e in iv))
    # modules run on the device, so busy time covers every decode run
    assert busy >= sum(devtrace.module_times(tr, "jit_decode_step")) * 0.5


def test_breakdown_lists(tr):
    ops = devtrace.top_ops(tr)
    gaps = devtrace.idle_gaps(tr)
    assert 0 < len(ops) <= 10 and 0 < len(gaps) <= 10
    assert ops == sorted(ops, key=lambda x: -x[1])
    assert gaps == sorted(gaps, key=lambda x: -x[1])
    busy = devtrace.busy_s(tr)
    assert sum(g[1] for g in gaps) <= tr.window_s - busy + 1e-9
    assert all(isinstance(n, str) for n, _ in gaps)


def test_merge_clips_and_joins():
    assert devtrace.merge([(0, 2), (1, 3), (5, 6), (9, 12)], 1, 10) == \
        [(1, 3), (5, 6), (9, 10)]
